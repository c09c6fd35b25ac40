"""The harness and the traffic generator on the CPU: seeded traffic, the
files found by name (an architecture's program adapter and reference
among them), the benchmark's own file, and whole runs of a tiny cell
(``bench/tests/tiny.py``) through the timed path."""
import json
import math
import re
import shutil
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
ARCHS = sorted(p.stem for p in (REPO / "bench" / "references").glob("*.py")
               if p.stem != "__init__")


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


def source_files(d: Path) -> dict:
    return {p: p.read_bytes() for p in d.rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


def test_architecture_modules_are_found_by_name():
    cfg = registry.config("qwen2-1.5b")
    assert cfg["architecture"] == "dense_gqa"
    program, ref = registry.program(cfg), registry.reference(cfg)
    assert Path(program.__file__) == REPO / "bench" / "programs" / "dense_gqa.py"
    assert Path(ref.__file__) == REPO / "bench" / "references" / "dense_gqa.py"
    for name in ("program_config", "params", "pair_counts"):
        assert callable(getattr(program, name))
    for name in ("layout", "served_gaps", "logits", "gemms", "flops_per_position",
                 "attention_work", "head_flops"):
        assert callable(getattr(ref, name))
    assert program.program_config(cfg).n_layers == 28
    assert ref.gemms(cfg)["w_down"] == (8960, 1536, True)


@pytest.mark.parametrize("kind", ["program", "reference"])
def test_an_unknown_architecture_names_the_file_it_looked_for(kind):
    cfg = {"name": "x", "architecture": "no_such_arch"}
    want = REPO / "bench" / f"{kind}s" / "no_such_arch.py"
    with pytest.raises(FileNotFoundError, match=re.escape(str(want))):
        getattr(registry, kind)(cfg)
    with pytest.raises(KeyError, match="names no architecture"):
        getattr(registry, kind)({"name": "x"})


def test_an_unknown_architecture_fails_before_any_device_work(tmp_path):
    """On the CPU with ``require_tpu``, the look for a chip would end the
    run; the missing adapter is found first."""
    root = tiny.make_root(tmp_path, config={**tiny.CONFIG, "architecture": "no_such_arch"})
    with pytest.raises(FileNotFoundError, match=re.escape("programs/no_such_arch.py")):
        run.run_cell("tiny.decode", 1, 1.0, False, root=root, require_tpu=True)


@pytest.mark.parametrize("arch", ARCHS)
def test_the_program_holds_the_references_layout(arch):
    """Each architecture's adapter, at its tiny size, builds a tree with
    exactly the leaves its reference draws (``tiny_configs/<arch>.json``)."""
    import jax

    cfg = tiny.tiny_config(arch)
    program, ref = registry.program(cfg), registry.reference(cfg)
    params = program.params(program.program_config(cfg), cfg, 3)
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    assert {weights.path_str(p): tuple(x.shape) for p, x in flat} == ref.layout(cfg)
    run.build_params(run.program_config(cfg), cfg, 3)  # checks the same, raising
    short = dict(ref.layout(cfg))
    short.popitem()
    with pytest.raises(ValueError, match="differ from the reference layout"):
        run.check_layout(params, short)


def test_an_architecture_joins_by_files_alone(tmp_path):
    """A second architecture, added to a checkout root as a program adapter
    and a reference (copies of dense GQA's under another name), a
    configuration, a cell and entries in BENCHMARK.json, runs correct, and
    no file of bench/ changes.  The originals leave the root, so the run
    can only have used the copies."""
    before = source_files(REPO / "bench")
    root = tiny.make_root(tmp_path)
    for kind in ("programs", "references"):
        d = root / "bench" / kind
        shutil.copy(d / "dense_gqa.py", d / "twin.py")
        (d / "dense_gqa.py").unlink()
    cfg = {**tiny.CONFIG, "name": "twin", "architecture": "twin"}
    (root / "bench" / "configs" / "twin.json").write_text(json.dumps(cfg))
    (root / "bench" / "cells" / "twin.decode.json").write_text(json.dumps(tiny.CELL))
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "twin", "source": cfg["source"],
                         "file": "bench/configs/twin.json", "reduced": [], "why": "CPU test"})
    b["workloads"].append({"name": "twin.decode", "config": "twin", "traffic": "tiny",
                           "chips": 1, "why": "CPU test"})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    assert Path(registry.reference(cfg, root).__file__) == root / "bench/references/twin.py"
    r = run.run_cell("twin.decode", 2**33 + 9, 1.5, False, root=root, require_tpu=False)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert r["check"]["max_logit_gap"]["value"] <= r["check"]["max_logit_gap"]["limit"]
    assert source_files(REPO / "bench") == before


def test_benchmark_file_keeps_to_its_contract():
    assert BENCH["command"] == ["python3", "bench/run.py"] and BENCH["paths"] == ["bench"]
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    cells = {w["name"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert NAME.match(c["name"]) and (REPO / c["file"]).is_file()
        assert c["reduced"] == registry.config(c["name"])["reduced"]
        arch = registry.config(c["name"])["architecture"]
        for kind in ("programs", "references"):
            assert (REPO / "bench" / kind / f"{arch}.py").is_file()
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


MAPPED_BYTES = """
import ctypes, sys
sys.path.insert(0, {root!r})
import numpy as np
from bench import run

class Info(ctypes.Structure):
    _fields_ = [(n, ctypes.c_size_t) for n in ("arena", "ordblks", "smblks", "hblks", "hblkhd",
                "usmblks", "fsmblks", "uordblks", "fordblks", "keepcost")]

libc = ctypes.CDLL(None)
libc.mallinfo2.restype = Info
kept = run.keep_host_pages() if {keep} else False
before = libc.mallinfo2().hblkhd
logits = np.ones((64, 151936), np.float32)
print(kept, libc.mallinfo2().hblkhd - before)
"""


@pytest.mark.parametrize("keep", [False, True])
def test_kept_host_pages_take_the_logits_buffer_from_the_heap(keep):
    """With ``keep_host_pages`` a step's 38.9 MB logits buffer comes from
    glibc's heap, not from a mapping of its own; without it, it is mapped."""
    p = subprocess.run([sys.executable, "-c", MAPPED_BYTES.format(root=str(REPO), keep=keep)],
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr
    kept, mapped = p.stdout.split()
    assert kept == str(keep)
    assert (int(mapped) == 0) if keep else (int(mapped) >= 64 * 151936 * 4)
