"""The harness and the traffic generator on the CPU: seeded traffic, the
files found by name, the benchmark's own file, and whole runs of a tiny
cell (``bench/tests/tiny.py``) through the timed path."""
import json
import math
import re
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench import registry, run, traffic, weights
from bench.tests import tiny

REPO = Path(__file__).resolve().parents[2]
BENCH = registry.benchmark()
MIXES = sorted(p.stem for p in (REPO / "bench" / "traffic").glob("*.json"))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def take(mix, seed, n, vocab=1000):
    """The first ``n`` requests of ``mix["block"]`` clients, taken in turn."""
    slots = mix["block"]
    streams = traffic.clients(mix, vocab, seed, slots)
    return [next(streams[i % slots]) for i in range(n)]


def lengths_by_client(mix, seed, slots, n):
    streams = traffic.clients(mix, 1000, seed, slots)
    return [[(len(r.prompt), r.max_new) for r in (next(c) for _ in range(n))] for c in streams]


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_requests(mix):
    m = registry.traffic(mix)
    a, b = take(m, 2**33 + 1, 150), take(m, 2**33 + 1, 150)
    assert [len(r.prompt) for r in a] == [len(r.prompt) for r in b]
    assert [r.max_new for r in a] == [r.max_new for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b, strict=True))


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_offers_the_same_lengths_in_each_block(mix):
    m = registry.traffic(mix)
    n = m["block"]
    a, b = take(m, 1, 2 * n), take(m, 2, 2 * n)
    for blk in (slice(0, n), slice(n, 2 * n)):
        assert sorted(len(r.prompt) for r in a[blk]) == sorted(len(r.prompt) for r in b[blk])
        assert sorted(r.max_new for r in a[blk]) == sorted(r.max_new for r in b[blk])
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_deals_the_same_clients_to_the_slots(mix):
    m = registry.traffic(mix)
    a = lengths_by_client(m, 5, 16, 2 * m["block"])
    b = lengths_by_client(m, 2**31 + 9, 16, 2 * m["block"])
    assert sorted(a) == sorted(b) and a != b


@pytest.mark.parametrize("mix", MIXES)
def test_each_client_sees_the_whole_distribution_in_each_block(mix):
    m = registry.traffic(mix)
    n = m["block"]
    want = sorted(traffic.quantile_lengths(m["prompt"], n)), sorted(
        traffic.quantile_lengths(m["output"], n))
    for seq in lengths_by_client(m, 6, 3, 2 * n):
        for blk in (seq[:n], seq[n:]):
            assert (sorted(p for p, _ in blk), sorted(o for _, o in blk)) == want


@pytest.mark.parametrize("mix", MIXES)
def test_every_prompt_is_at_least_a_prefill_chunk(mix):
    m = registry.traffic(mix)
    assert min(len(r.prompt) for r in take(m, 3, 4 * m["block"])) >= m["prefill_chunk"]


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_prompt_plus_output_fits_the_cache(cell):
    wl = registry.workload(cell)
    m, c = registry.traffic(wl["traffic"]), registry.cell(cell)
    traffic.validate(m, c["max_seq"])
    longest = max(len(r.prompt) + r.max_new for r in take(m, 4, 4 * m["block"]))
    assert longest < c["max_seq"]


def test_lengths_follow_the_stated_distribution():
    m = registry.traffic("decode")
    lens = traffic.quantile_lengths(m["prompt"], 1001)
    assert np.median(lens) == m["prompt"]["median"]
    assert lens.min() == m["prompt"]["min"] and lens.max() == m["prompt"]["max"]


def test_large_seeds_are_not_folded_to_32_bits():
    import jax

    a = jax.random.key_data(weights.seed_key(5))
    b = jax.random.key_data(weights.seed_key(2**32 + 5))
    assert not np.array_equal(np.asarray(a), np.asarray(b))


def test_new_files_are_found_by_name(tmp_path):
    """A configuration, a traffic mix, a cell and a metric added as files,
    with entries in BENCHMARK.json, and no edit to any file in bench/."""
    before = {p: p.read_bytes() for p in (REPO / "bench").rglob("*") if p.is_file()}
    root = tiny.make_root(tmp_path)
    (root / "bench" / "metrics" / "answer_count.py").write_text(
        "def read(run):\n    return float(len(run.completed))\n")
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["per_layer"].append({"name": "answer_count", "unit": "requests", "better": "higher",
                           "source": "program_counter", "layer": "engine",
                           "moves": "tokens_per_s", "workloads": ["tiny.decode"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    assert registry.config("tiny", root)["hidden_size"] == 64
    assert registry.traffic("tiny", root)["prefill_chunk"] == 4
    assert registry.cell("tiny.decode", root)["batch_slots"] == 4
    names = [m["name"] for m in registry.metrics("tiny.decode", "per_layer", root)]
    assert "answer_count" in names
    read = registry.reader("answer_count", root)
    assert read(type("Run", (), {"completed": [1, 2, 3]})) == 3.0
    after = {p: p.read_bytes() for p in (REPO / "bench").rglob("*") if p.is_file()}
    assert after == before


def test_benchmark_file_keeps_to_its_contract():
    assert BENCH["command"] == ["python3", "bench/run.py"] and BENCH["paths"] == ["bench"]
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    cells = {w["name"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert NAME.match(c["name"]) and (REPO / c["file"]).is_file()
        assert c["reduced"] == registry.config(c["name"])["reduced"]
    for w in BENCH["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert (REPO / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
        assert (REPO / "bench" / "cells" / f"{w['name']}.json").is_file()
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and m["better"] in ("lower", "higher")
        assert (REPO / "bench" / "metrics" / f"{m['name']}.py").is_file()
        assert set(m.get("workloads", cells)) <= cells
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        reporting = {w for w in cells if w in next(
            e.get("workloads", cells) for e in BENCH["end_to_end"] if e["name"] == m["moves"])}
        assert set(m.get("workloads", cells)) <= reporting
        if m["unit"] == "%" and "roofline" in m["name"]:
            assert m["name"].endswith("_roofline")


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("tiny"))


def test_tiny_cell_runs_correct(tiny_root):
    r = run.run_cell("tiny.decode", 2**33 + 7, 1.5, False, root=tiny_root, require_tpu=False)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"tokens_per_s", "itl_p95_ms", "ttft_p95_ms", "setup_s"}
    assert all(math.isfinite(m["value"]) and m["value"] > 0 for m in r["metrics"].values())
    assert list(r)[-1] == "check"
    assert r["check"]["max_logit_gap"]["value"] <= r["check"]["max_logit_gap"]["limit"]


def test_without_a_tpu_the_run_prints_no_result():
    p = subprocess.run(
        [sys.executable, str(REPO / "bench" / "run.py"), "--workload", "qwen2-1.5b.decode",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr
