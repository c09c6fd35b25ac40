"""Operations and bytes (``bench.work``) and the table of peaks."""
import numpy as np
import pytest

from bench import peaks, reference, registry, work


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
    got = work.dense_flops_per_position(c.n_layers, c.d_model, c.n_heads, c.n_kv_heads,
                                        c.head_dim, c.d_ff)
    assert got == want
    assert work.head_flops(c.d_model, c.vocab) == 2 * c.d_model * c.vocab


def test_sizes_of_the_benchmark_config():
    s = reference.sizes(registry.config("qwen2-1.5b"))
    assert (s["L"], s["d"], s["H"], s["KH"], s["hd"], s["f"], s["V"], s["Vp"]) == (
        28, 1536, 12, 2, 128, 8960, 151936, 151936)


def test_decode_attention_counts_live_positions_only():
    a = work.decode_attention(100, 4, 12, 2, 128, 1536, 700, 136)
    b = work.decode_attention(200, 4, 12, 2, 128, 1536, 700, 136)
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
