"""The reduction of the program's own spans, programs and scopes from a
profiler trace (``bench.program_trace``) and the five metrics read from it:
on an ``XSpace`` built here by hand, on the trace recorded on a TPU v5e
before the program had them (``data/v5e_tiny_decode.xplane.pb``), and on
one recorded with them (``data/v5e_tiny_serve.xplane.pb``).

The second recording: qwen2-1.5b widths with 2 layers, paired at r=0.05,
8 slots of 256 positions; under the profiler one release and one admission
(a 16-token prefill and a forced token), then four decode steps, each
followed by two forced tokens, with the client's span names around the
engine calls.
"""
import dataclasses
import types
from pathlib import Path

import pytest

from bench import program_trace as pt
from bench import registry

DATA = Path(__file__).resolve().parent / "data"
OLD = DATA / "v5e_tiny_decode.xplane.pb"
NEW = DATA / "v5e_tiny_serve.xplane.pb"
METRICS = ("decode_device_ms", "refold_device_ms", "logits_copy_idle_ms", "force_idle_ms",
           "d2h_bytes_per_step")


# -- an XSpace by hand ---------------------------------------------------------

def varint(n: int) -> bytes:
    n &= (1 << 64) - 1
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def field(num: int, value) -> bytes:
    if isinstance(value, int):
        return varint(num << 3) + varint(value)
    if isinstance(value, str):
        value = value.encode()
    return varint(num << 3 | 2) + varint(len(value)) + value


def stat(mid, value):
    return field(1, mid) + (field(4, value) if isinstance(value, int) else field(5, value))


def plane(name, events, stat_names, lines):
    """``events``: {id: (name, {stat id: value})}; ``lines``: {name: [(metadata
    id, offset ps, duration ps, {stat id: value})]} at line timestamp 1 ms."""
    out = field(2, name)
    for lname, evs in lines.items():
        body = field(2, lname) + field(3, 1_000_000)
        for mid, off, dur, stats in evs:
            ev = field(1, mid) + field(2, off) + field(3, dur)
            ev += b"".join(field(4, stat(k, v)) for k, v in stats.items())
            body += field(4, ev)
        out += field(3, body)
    for k, (ename, stats) in events.items():
        meta = field(1, k) + field(2, ename) + b"".join(
            field(5, stat(s, v)) for s, v in stats.items())
        out += field(4, field(1, k) + field(2, meta))
    for k, sname in stat_names.items():
        out += field(5, field(1, k) + field(2, field(1, k) + field(2, sname)))
    return out


def space():
    """Two decode steps.  Device: a ``serve_decode`` run per step, each a loop
    around a refold gather and an attention op, and an eager ``argmax``.
    Host: ``engine.step`` spans with their children, a forced token, and
    the client's ``step`` span around the engine's."""
    us = 1_000_000  # ps
    TF, PID = 1, 2
    dev_events = {
        1: ("jit_serve_decode(77)", {}),
        2: ("jit__argmax(88)", {}),
        3: ("%while.1 = while()", {TF: "jit(serve_decode)/layer_scan/while:", PID: 77}),
        4: ("%fusion.2 = gather()", {
            TF: "jit(serve_decode)/layer_scan/while/body/closed_call/mlp/refold/jit(_take)/gather:",
            PID: 77}),
        5: ("%fused_attn_decode.3 = custom-call()", {
            TF: "jit(serve_decode)/layer_scan/while/body/closed_call/attn/pallas_call:", PID: 77}),
        6: ("%reduce.4 = reduce()", {TF: "jit(_argmax)/reduce:", PID: 88}),
    }
    ops, modules = [], []
    for k in range(2):  # step k runs on the device at [100k, 100k + 40) us
        t = 100 * k * us
        modules += [(1, t, 40 * us, {}), (2, t + 50 * us, 5 * us, {})]
        ops += [(3, t, 40 * us, {}), (4, t, 30 * us, {}), (5, t + 30 * us, 10 * us, {}),
                (6, t + 50 * us, 5 * us, {})]
    dev = plane("/device:TPU:0", dev_events, {TF: "tf_op", PID: "program_id"},
                {"XLA Modules": modules, "XLA Ops": ops})
    B, ACT, D2H, SLOT, REQ = 1, 2, 3, 4, 5
    host_events = {10: ("step", {}), 11: ("engine.step", {}), 12: ("engine.step.dispatch", {}),
                   13: ("engine.step.logits_to_host", {}), 14: ("engine.force", {})}
    spans = []
    for k in range(2):
        t = 100 * k * us
        spans += [(10, t, 70 * us, {}), (11, t, 70 * us, {ACT: 4, D2H: 1000 + k}),
                  (12, t, 2 * us, {}), (13, t + 2 * us, 60 * us, {B: 992}),
                  (14, t + 80 * us, 10 * us, {SLOT: 1, REQ: 7})]
    host = plane("/host:CPU", host_events,
                 {B: "bytes", ACT: "active", D2H: "d2h_bytes", SLOT: "slot", REQ: "req"},
                 {"python3": spans})
    return field(1, dev) + field(1, host)


@pytest.fixture
def hand(tmp_path):
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(space())
    return path


def make_run(path, steps):
    return types.SimpleNamespace(trace=object(), trace_path=path, workload="hand",
                                 steps=[None] * steps)


def test_the_reduction_by_hand(hand):
    tr = pt.load(hand)
    assert [s.name for s in tr.spans[:4]] == [
        "engine.step", "engine.step.dispatch", "engine.step.logits_to_host", "engine.force"]
    assert [s.depth for s in tr.spans[:4]] == [0, 1, 1, 0]
    assert tr.spans[0].args == {"active": 4, "d2h_bytes": 1000}
    assert tr.named("engine.force")[0].args == {"slot": 1, "req": 7}
    assert [p for p, _, _ in tr.modules] == ["serve_decode", "_argmax"] * 2
    us = 1_000_000
    # the loop's self time is its duration less the two ops inside it
    loops = [o for o in tr.ops if o.path.endswith("/while")]
    assert [o.self_ps for o in loops] == [0, 0] and loops[0].scope == "layer_scan"
    gathers = [o for o in tr.ops if o.scope == "refold"]
    assert len(gathers) == 2 and all(o.program == "serve_decode" for o in gathers)
    assert tr.path_stats(gathers[0].path) == (2, pytest.approx(60e-6))
    # step k: device busy [0, 40) and [50, 55) us; the copy span is [2, 62)
    assert tr.idle_seconds(tr.named("engine.step.logits_to_host")) == pytest.approx(2 * 17e-6)
    idle = tr.idle_by_span()
    assert idle["engine.step.logits_to_host"] == pytest.approx(2 * 17e-6)
    assert idle["engine.step"] == pytest.approx(2 * 8e-6)  # [62, 70) under the parent alone
    assert idle["engine.force"] == pytest.approx(2 * 10e-6)
    # [70, 80) and [90, 100) after step 0, [170, 180) after step 1
    assert idle[pt.UNSCOPED] == pytest.approx(30e-6)
    t0 = tr.window[0]  # the lines' timestamp
    assert tr.busy_within(t0, t0 + 100 * us) == 45 * us
    assert tr.busy_within(t0 + 35 * us, t0 + 52 * us) == 7 * us


def test_the_five_metrics_by_hand(hand):
    run = make_run(hand, 2)
    got = {m: registry.reader(m)(run) for m in METRICS}
    assert got == pytest.approx({"decode_device_ms": 0.040, "refold_device_ms": 0.030,
                                 "logits_copy_idle_ms": 0.017, "force_idle_ms": 0.010,
                                 "d2h_bytes_per_step": 1000.5})


def test_the_metrics_read_nothing_when_the_steps_do_not_match(hand):
    for steps in (1, 3):
        assert all(registry.reader(m)(make_run(hand, steps)) is None for m in METRICS)


def test_an_untraced_run_reads_nothing(hand):
    run = make_run(hand, 2)
    run.trace = None
    assert all(registry.reader(m)(run) is None for m in METRICS)


# -- the trace recorded before the program had spans and scopes ---------------

def test_recorded_trace_without_spans_or_scopes():
    """The programs of the older engine, named after its local functions,
    and xprof's ``framework_op_stats`` reading of the refold's gathers."""
    tr = pt.load(OLD)
    decode = tr.module_runs("decode_fn")
    prefill = tr.module_runs("prefill_fn")
    assert len(decode) == 4 and sum(b - a for a, b in decode) * 1e-6 == pytest.approx(9228.6, abs=0.05)
    assert len(prefill) == 1 and sum(b - a for a, b in prefill) * 1e-6 == pytest.approx(
        2245.9, abs=0.05)
    n, s = tr.path_stats("jit(decode_fn)/while/body/closed_call/jit(_take)/gather")
    assert n == 8 and s * 1e6 == pytest.approx(4168.1, abs=0.05)
    assert not tr.spans and not tr.module_runs(pt.DECODE)
    run = make_run(OLD, 4)
    assert all(registry.reader(m)(run) is None for m in METRICS)


def test_the_decoder_agrees_with_profile_data():
    from jax.profiler import ProfileData

    tr = pt.load(OLD)
    dev = next(p for p in ProfileData.from_file(str(OLD)).planes if p.name == pt.DEVICE_PLANE)
    lines = {ln.name: sorted((e.start_ns, e.duration_ns) for e in ln.events) for ln in dev.lines}
    ours = sorted((a / 1000, (b - a) / 1000) for _, a, b in tr.modules)
    assert len(ours) == len(lines[pt.MODULES_LINE])
    for (a, d), (a2, d2) in zip(ours, lines[pt.MODULES_LINE], strict=True):
        assert a == pytest.approx(a2, abs=1) and d == pytest.approx(d2, abs=1)
    assert len(tr.ops) == len(lines[pt.OPS_LINE])


# -- the trace recorded with them ------------------------------------------------

STEPS, SLOTS, VOCAB = 4, 8, 151936


def test_recorded_trace_with_spans_and_scopes():
    tr = pt.load(NEW)
    assert len(tr.module_runs(pt.DECODE)) == STEPS and len(tr.module_runs(pt.PREFILL)) == 1
    counts = {n: len(tr.named(n)) for n in ("engine.step", "engine.force", "engine.admit",
                                            "engine.release", "engine.step.logits_to_host")}
    assert counts == {"engine.step": STEPS, "engine.force": 1 + 2 * STEPS, "engine.admit": 1,
                      "engine.release": 1, "engine.step.logits_to_host": STEPS}
    scopes = {o.scope for o in tr.ops if o.program == pt.DECODE}
    assert {"embed", "attn", "mlp", "cache_update", "head", "refold", "layer_scan"} <= scopes
    assert {o.scope for o in tr.ops if o.program == pt.PREFILL} >= {"attn", "mlp", "refold"}


def test_the_five_metrics_on_the_recorded_trace():
    got = {m: registry.reader(m)(make_run(NEW, STEPS)) for m in METRICS}
    assert got["d2h_bytes_per_step"] == SLOTS * VOCAB * 4 + 2 * SLOTS * 4
    assert all(v is not None and v > 0 for v in got.values()), got
    assert got["refold_device_ms"] < got["decode_device_ms"]


@pytest.mark.parametrize("missing", ["engine.step", "engine.step.logits_to_host",
                                     "engine.force", pt.DECODE])
def test_a_missing_span_or_program_reads_nothing(monkeypatch, missing):
    tr = pt.load(NEW)
    cut = dataclasses.replace(
        tr, spans=[s for s in tr.spans if s.name != missing],
        modules=[m for m in tr.modules if m[0] != missing])
    monkeypatch.setattr(pt, "for_run", lambda run: cut)
    got = {m: registry.reader(m)(make_run(NEW, STEPS)) for m in METRICS}
    reads = {"engine.step": "d2h_bytes_per_step",
             "engine.step.logits_to_host": "logits_copy_idle_ms",
             "engine.force": "force_idle_ms"}
    if missing == pt.DECODE:
        assert all(v is None for v in got.values())
    else:
        assert got.pop(reads[missing]) is None
        assert all(v is not None for v in got.values())
