"""The serving engine's own instrumentation: profiler spans with their
parents and args, counters, and the named scopes of its jitted steps.

At the tiny qwen2-shaped configuration the benchmark's CPU tests use
(``bench/tests/tiny.py``): paired GEMMs and the fused decode attention in
interpret mode.  The spans are read back with ``jax.profiler.ProfileData``,
nothing of the benchmark's own reduction.
"""
import glob

import jax
import jax.extend.core as jec
import numpy as np
import pytest
from jax.profiler import ProfileData

from bench import run as bench_run
from bench import trace_reduce
from bench.tests import tiny
from repro.models import lm as M
from repro.serving import trace
from repro.serving.engine import ServeEngine

BATCH, MAX_SEQ, PLEN = 4, 32, 6
#: the parent of every engine span, as the engine nests them
PARENT = {
    "engine.admit": None, "engine.step": None, "engine.force": None,
    "engine.release": None,
    "engine.admit.prefill": "engine.admit", "engine.admit.splice": "engine.admit",
    "engine.admit.first_token": "engine.admit",
    "engine.step.dispatch": "engine.step", "engine.step.sample": "engine.step",
    "engine.step.logits_to_host": "engine.step", "engine.step.tokens_to_host": "engine.step",
    "engine.release.scrub": "engine.release",
}
ARGS = {
    "engine.admit": {"slot", "req", "prompt_tokens"},
    "engine.admit.prefill": {"traces"},
    "engine.admit.first_token": {"bytes"},
    "engine.step": {"step", "active", "forced", "d2h_bytes"},
    "engine.step.dispatch": {"traces"},
    "engine.step.logits_to_host": {"bytes"},
    "engine.step.tokens_to_host": {"bytes"},
    "engine.force": {"slot", "req"},
    "engine.release": {"slot", "req"},
}


def make_engine():
    cfg = tiny.CONFIG
    pcfg = bench_run.program_config(cfg)
    dep = cfg["deployment"]
    knobs = M.PerfKnobs(q_chunk=dep["q_chunk"], k_chunk=dep["k_chunk"], remat="none",
                        gemm=dep["gemm"], attn=dep["attn"], pair_rounding=dep["pair_rounding"],
                        pair_block_n=dep["pair_block_n"])
    params = bench_run.build_params(pcfg, cfg, 3)
    return ServeEngine(pcfg, params, max_seq=MAX_SEQ, batch_size=BATCH, knobs=knobs)


def prompt(seed, n=PLEN):
    return np.random.default_rng(seed).integers(0, tiny.CONFIG["vocab_size"], n).astype(np.int32)


@pytest.fixture(scope="module")
def engine():
    """An engine with every slot admitted and two steps taken: every shape
    the traced calls use has been compiled."""
    eng = make_engine()
    for slot in range(BATCH):
        eng.add_request(slot, prompt(slot))
    eng.step()
    eng.force_token(0, 5)
    eng.step()
    eng.release_slot(1)
    eng.add_request(1, prompt(9))
    return eng


def host_spans(path):
    """(name, start, end, args) of every ``engine.*`` host event."""
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("engine."):
                    out.append((e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats)))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def parent_of(span, spans):
    """The innermost other span that encloses ``span``."""
    inside = [s for s in spans if s is not span and s[1] <= span[1] and span[2] <= s[2]]
    return min(inside, key=lambda s: s[2] - s[1])[0] if inside else None


@pytest.fixture(scope="module")
def traced(engine, tmp_path_factory):
    """One request's life under the profiler: release, admit, a forced token,
    two steps, release."""
    eng = engine
    d = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(d)):
        eng.release_slot(2)
        eng.add_request(2, prompt(11))
        eng.force_token(2, 7)
        eng.step()
        eng.step()
        eng.release_slot(2)
    return host_spans(sorted(glob.glob(f"{d}/**/*.xplane.pb", recursive=True))[-1])


def test_every_span_is_emitted_with_its_parent_and_args(traced):
    names = {s[0] for s in traced}
    assert names == set(trace.SPANS) == set(PARENT)
    for s in traced:
        assert parent_of(s, traced) == PARENT[s[0]], s[0]
        assert ARGS.get(s[0], set()) <= set(s[3]), (s[0], s[3])
    steps = [s for s in traced if s[0] == "engine.step"]
    assert len(steps) == 2 and all(s[3]["active"] == BATCH for s in steps)
    assert steps[1][3]["step"] == steps[0][3]["step"] + 1
    # the tokens, copied once a step (the positions stay on the host)
    assert sum(s[0] == "engine.step.tokens_to_host" for s in traced) == 2


def test_req_ties_an_admission_to_its_forced_tokens_and_release(traced):
    by = {n: [s[3] for s in traced if s[0] == n]
          for n in ("engine.admit", "engine.force", "engine.release")}
    (admit,) = by["engine.admit"]
    assert admit["slot"] == 2 and admit["prompt_tokens"] == PLEN
    assert [f["req"] for f in by["engine.force"]] == [admit["req"]]
    released = [r["req"] for r in by["engine.release"] if r["slot"] == 2]
    assert released[-1] == admit["req"] and released[0] != admit["req"]


def test_no_engine_span_collides_with_a_client_span():
    assert all(n.startswith("engine.") for n in trace.SPANS)
    assert not set(trace.SPANS) & set(trace_reduce.SPANS)


def test_counters_count_steps_and_host_bytes():
    eng = make_engine()
    for slot in range(BATCH):
        eng.add_request(slot, prompt(20 + slot))
    c = eng.counters
    assert (c.admissions, c.steps, c.d2h_bytes) == (BATCH, 0, BATCH * 4)
    before = c.d2h_bytes
    for _ in range(3):
        eng.step()
    eng.force_token(1, 3)
    eng.release_slot(0)
    vocab = eng.cfg.vocab
    assert c.d2h_bytes - before == 3 * (BATCH * vocab * 4 + BATCH * 4)
    assert (c.steps, c.forced_tokens, c.releases) == (3, 1, 1)


def test_step_counts_the_tokens_forced_since_the_last_step(engine, tmp_path):
    eng = engine
    eng.step()  # takes in whatever an earlier test forced
    with jax.profiler.trace(str(tmp_path)):
        eng.force_token(0, 3)
        eng.force_token(3, 4)
        eng.step()
        eng.step()
        eng.force_token(1, 5)
        eng.step()
    spans = host_spans(sorted(glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True))[-1])
    assert [s[3]["forced"] for s in spans if s[0] == "engine.step"] == [2, 0, 1]


def test_traces_do_not_grow_over_repeated_steps(engine):
    eng = engine
    n = eng.counters.traces
    assert n >= 2  # the decode and the prefill step, each traced once
    for _ in range(3):
        eng.step()
    eng.release_slot(3)
    eng.add_request(3, prompt(12))
    assert eng.counters.traces == n


def _op_names(compiled_text):
    return " ".join(line.split('op_name="', 1)[1].split('"', 1)[0]
                    for line in compiled_text.splitlines() if 'op_name="' in line)


def test_steps_carry_the_named_scopes(engine):
    eng = engine
    dec = _op_names(eng._decode.lower(eng.params, eng.cache, eng.tokens, eng.pos)
                    .compile().as_text())
    pre = _op_names(eng._prefill.lower(eng.params, {"tokens": prompt(1)[None]})
                    .compile().as_text())
    assert "jit(serve_decode)/" in dec and "jit(serve_prefill)/" in pre
    for scope in ("embed", "attn", "mlp", "cache_update", "head", "refold", "layer_scan"):
        assert f"/{scope}/" in dec, scope
    # prefill's K/V leave each layer as the layer scan's stacked output
    for scope in ("embed", "attn", "mlp", "head", "refold", "layer_scan"):
        assert f"/{scope}/" in pre, scope


def _eqns(jaxpr, prefix=""):
    """Every equation of a jaxpr and its sub-jaxprs, with its full scope path."""
    for eqn in jaxpr.eqns:
        ns = str(eqn.source_info.name_stack)
        path = f"{prefix}/{ns}" if ns else prefix
        yield eqn, path
        for p in eqn.params.values():
            for sub in p if isinstance(p, (list, tuple)) else [p]:
                if isinstance(sub, jec.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, jec.Jaxpr):
                    yield from _eqns(sub, path)


@pytest.mark.parametrize("step", ["decode", "prefill"])
def test_every_gather_of_a_paired_weight_lies_under_refold(engine, step):
    eng = engine
    if step == "decode":
        jx = jax.make_jaxpr(eng._decode.__wrapped__)(eng.params, eng.cache, eng.tokens, eng.pos)
    else:
        jx = jax.make_jaxpr(eng._prefill.__wrapped__)(eng.params, {"tokens": prompt(1)[None]})
    segment_fns = {"_lm_structured_segments", "_take_block_segments"}
    weight_gathers = [
        path for eqn, path in _eqns(jx.jaxpr) if eqn.primitive.name == "gather"
        and segment_fns & {f.function_name for f in eqn.source_info.traceback.frames}]
    # three row gathers (I, J, resid) of each paired weight
    assert len(weight_gathers) >= 3 * 6
    assert all("/refold" in p for p in weight_gathers), weight_gathers
