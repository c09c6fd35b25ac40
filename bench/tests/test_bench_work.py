"""Operations and bytes (``bench.work`` and each reference's counts), the
table of peaks, and the readers that count work, pinned on a trace recorded
on a TPU v5e."""
import types
from pathlib import Path

import numpy as np
import pytest

from bench import peaks, registry, trace_reduce, work
from bench.driver import Admit, Step
from bench.references import dense_gqa
from bench.tests.tiny import bench_config

DATA = Path(__file__).resolve().parent / "data"


def test_paired_gemm_work_from_a_known_pairing():
    from repro.core.pairing import pair_rows_structured

    # rows 0/1 and 2/3 cancel in sign (rms of their sums 0.1 < 0.5); row 4
    # has no partner: P = 2 pairs, R = 1 residual row
    W = np.array([[1.0, 1.0], [-0.9, -1.1], [2.0, 2.0], [-2.1, -1.9], [0.5, 0.7]])
    sp = pair_rows_structured(W, 0.5)
    P, R = sp.n_pairs, len(sp.resid)
    assert (P, R) == (2, 1)
    M, N = 8, W.shape[1]
    w = work.paired_gemm(M, N, P, R, residual=True)
    # contraction over P + R = 3 lanes for each of M x N outputs, plus M x P subtracts
    assert w.flops == 2 * M * N * 3 + M * 2
    # x[I], x[J], x[R]; Kmat and W_R; the fused residual; the output
    assert w.bytes == 2 * (M * (2 * 2 + 1) + 3 * N + M * N + M * N)
    dense = work.paired_gemm(M, N, 0, W.shape[0])
    assert dense.flops > w.flops


@pytest.mark.parametrize("arch, want", [
    # 2 x (wq + wk + wv + wo + gate + up + down) per layer, 28 and 40 layers
    ("qwen2-1.5b", 28 * 2 * (1536 * 1536 + 2 * 1536 * 256 + 1536 * 1536 + 3 * 1536 * 8960)),
    ("granite-3-2b", 40 * 2 * (2048 * 2048 + 2 * 2048 * 512 + 2048 * 2048 + 3 * 2048 * 8192)),
])
def test_dense_flops_per_token(arch, want):
    from repro.configs import get_config

    c = get_config(arch)
    cfg = bench_config(c)
    assert dense_gqa.flops_per_position(cfg) == want
    assert dense_gqa.head_flops(cfg) == 2 * c.d_model * c.vocab


def test_sizes_of_the_benchmark_config():
    cfg = registry.config("qwen2-1.5b")
    s = registry.reference(cfg).sizes(cfg)
    assert (s["L"], s["d"], s["H"], s["KH"], s["hd"], s["f"], s["V"], s["Vp"]) == (
        28, 1536, 12, 2, 128, 8960, 151936, 151936)


def test_decode_attention_counts_live_positions_only():
    cfg = registry.config("qwen2-1.5b")
    a, b = (work.fused_out_projection(dense_gqa.attention_work(cfg, context, 4), 4,
                                      1536, 1536, 700, 136) for context in (100, 200))
    assert b.flops - a.flops == 4 * 12 * 128 * 100
    assert b.bytes - a.bytes == 2 * 2 * 128 * 100 * 2


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v99")
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12


def test_least_time_is_the_slower_bound():
    p = peaks.peaks_for("TPU v5 lite")
    assert peaks.least_time_s(197e12, 1.0, p) == pytest.approx(1.0)
    assert peaks.least_time_s(1.0, 819e9, p) == pytest.approx(1.0)


#: what the readers read on the recorded trace at the commit before the
#: work counts moved beside each architecture's reference
PINNED = {"step_mfu": 0.024767318546192893,
          "paired_matmul_roofline": 47.96436433255523,
          "fused_attn_decode_roofline": 17.225416846441828}


@pytest.mark.parametrize("metric", sorted(PINNED))
def test_work_readers_read_the_same_work_on_the_recorded_trace(metric):
    """``data/v5e_tiny_serve.xplane.pb`` (``test_program_trace.py``): qwen2's
    widths with 2 layers, 8 slots; one 16-token prefill and four decode
    steps, with fixed pair counts and contexts."""
    cfg = dict(registry.config("qwen2-1.5b"), num_hidden_layers=2)
    names = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
    pairs = {n: (np.array([700 + 10 * l + i for l in range(2)]), np.array([136, 137]))
             for i, n in enumerate(names)}
    run = types.SimpleNamespace(
        config=cfg, reference=registry.reference(cfg), pairs=pairs, batch_slots=8,
        trace=trace_reduce.summarize(DATA / "v5e_tiny_serve.xplane.pb"),
        steps=[Step(0.1 * k, 0.1 * k + 0.05, 8, 8 * (17 + k), 8) for k in range(4)],
        admits=[Admit(0.0, 0.02, 16)], device_kind="TPU v5 lite", n_chips=1, t0=0.0, t1=0.5)
    assert registry.reader(metric)(run) == pytest.approx(PINNED[metric], rel=1e-12)
