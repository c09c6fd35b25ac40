"""The reduction from a profiler trace to busy time, per-op time and the
breakdown (``bench.trace_reduce``): on planes built by hand, and on a small
trace recorded on a TPU v5e (``data/``)."""
import types
from pathlib import Path

import pytest

from bench import trace_reduce

DATA = Path(__file__).resolve().parent / "data"


def plane(name, lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=ln, events=[
            types.SimpleNamespace(name=n, start_ns=a, end_ns=b) for n, a, b in evs])
        for ln, evs in lines.items()])


def test_busy_idle_and_attribution_by_hand():
    host = plane("/host:CPU", {"python": [("admit", 0, 100), ("step", 100, 400),
                                          ("client", 400, 450), ("other", 0, 1000)]})
    dev = plane("/device:TPU:0", {
        "XLA Ops": [("%paired_matmul.3 = bf16[8] custom-call()", 20, 60),
                    ("%fusion.1 = bf16[8] fusion()", 50, 80),  # overlap: busy 20..80
                    ("%paired_matmul.4 = bf16[8] custom-call()", 150, 300),
                    ("%while.2 = () while()", 150, 300),  # a loop around its body's ops
                    ("%copy.9 = bf16[8] copy()", 440, 480)],  # ends after the last span
        "Steps": [("0", 0, 1000)]})
    s = trace_reduce.reduce_planes([host, dev])
    assert s.window_s == pytest.approx(480e-9)
    assert s.busy_s == pytest.approx((60 + 150 + 40) * 1e-9)
    assert s.op_seconds("paired_matmul") == (2, pytest.approx(190e-9))
    assert "while" not in s.ops and s.ops["fusion"] == [1, pytest.approx(30e-9)]
    # idle: 0..20 and 80..100 under admit, 100..150 and 300..400 under step,
    # 400..440 under client
    assert s.idle_by_span == pytest.approx({"admit": 40e-9, "step": 150e-9, "client": 40e-9})
    b = s.breakdown()
    assert b["device_ops"][0][0] == "paired_matmul"
    assert b["idle_gaps"][0] == ["step", pytest.approx(150e-9)]


def test_the_window_opens_at_the_first_device_op_when_it_leads_the_host():
    host = plane("/host:CPU", {"python": [("step", 100, 200)]})
    dev = plane("/device:TPU:0", {"XLA Ops": [("%paired_matmul.1 = x", 90, 150)]})
    s = trace_reduce.reduce_planes([host, dev])
    assert s.window_s == pytest.approx(110e-9) and s.op_seconds("paired_matmul")[0] == 1


def test_a_trace_without_client_spans_is_refused():
    with pytest.raises(ValueError, match="no client spans"):
        trace_reduce.reduce_planes([plane("/host:CPU", {"python": [("x", 0, 1)]})])


def test_recorded_v5e_trace():
    """Two layers of qwen2's widths on a v5e, paired at r=0.05: one
    admission (a 16-token prefill: 7 paired GEMMs per layer) and four
    decode steps of 8 slots (6 paired GEMMs and one fused attention per
    layer), with the client's spans."""
    s = trace_reduce.summarize(DATA / "v5e_tiny_decode.xplane.pb")
    assert s.op_seconds("paired_matmul")[0] == 2 * 7 + 4 * 2 * 6
    assert s.op_seconds("fused_attn_decode")[0] == 4 * 2
    assert 0 < s.busy_s < s.window_s
    assert "while" not in s.ops
    b = s.breakdown()
    assert len(b["device_ops"]) == 10 and b["device_ops"][0][1] >= b["device_ops"][-1][1]
    assert {k for k, _ in b["idle_gaps"]} <= {"admit", "step", "force", "client", "release", "loop"}
    assert sum(v for _, v in b["idle_gaps"]) == pytest.approx(s.window_s - s.busy_s, rel=1e-6)
