"""Pallas paired-matmul kernel vs pure-jnp oracle: shape/dtype sweeps,
K-tiling (block_k < K) edge cases, epilogue fusion, and property-based
equivalence with the folded dense matmul (seeded cases via _proptest)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _proptest import cases, integers, seeds
from repro.core.pairing import pair_rows_structured
from repro.kernels.ops import (
    apply_structured_pairing,
    dense_matmul,
    paired_matmul,
    pallas_gemm,
)
from repro.kernels.ref import dense_matmul_ref, paired_matmul_ref
from repro.kernels.tuning import choose_blocks


def _tol(dtype):
    # bf16: inputs are rounded to 8-bit mantissas before the fp32-accumulated
    # dot, and the kernel's VPU subtract happens pre-cast — tolerance follows
    # the FlashAttention/Triton convention for half-precision GEMM checks.
    if dtype == jnp.bfloat16:
        return dict(rtol=5e-2, atol=5e-2)
    return dict(rtol=1e-4, atol=1e-4)  # fp32: blocked vs unblocked accum order


def _rand_case(rng, M, P, R, N, dtype):
    x = jnp.asarray(rng.normal(size=(M, 2 * P + R)), dtype)
    kmat = jnp.asarray(rng.normal(size=(P, N)), dtype)
    w_res = jnp.asarray(rng.normal(size=(R, N)), dtype)
    return x, kmat, w_res


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "M,P,R,N",
    [
        (8, 16, 8, 32),
        (128, 128, 128, 128),
        (100, 60, 40, 50),  # non-multiples of the tile → padding path
        (256, 256, 0, 128),  # no residual
        (32, 0, 64, 64),  # no pairs
        (1, 8, 8, 8),  # single row (decode)
        (300, 100, 77, 200),
    ],
)
def test_paired_kernel_matches_ref(M, P, R, N, dtype):
    rng = np.random.default_rng(P * 1000 + R * 10 + N)
    x, kmat, w_res = _rand_case(rng, M, P, R, N, dtype)
    got = paired_matmul(x, kmat, w_res, block_m=64, block_n=64)
    want = paired_matmul_ref(x, kmat, w_res)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **_tol(dtype)
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dense_kernel_matches_ref(dtype):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(96, 160)), dtype)
    w = jnp.asarray(rng.normal(size=(160, 112)), dtype)
    got = dense_matmul(x, w, block_m=32, block_n=32)
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        np.asarray(dense_matmul_ref(x, w), np.float32),
        **_tol(dtype),
    )


@cases(15, M=integers(1, 40), P=integers(0, 24), R=integers(0, 24),
       N=integers(1, 32), seed=seeds())
def test_paired_kernel_property(M, P, R, N, seed):
    if P + R == 0:
        R = 1
    rng = np.random.default_rng(seed)
    x, kmat, w_res = _rand_case(rng, M, P, R, N, jnp.float32)
    got = paired_matmul(x, kmat, w_res, block_m=16, block_n=16)
    want = paired_matmul_ref(x, kmat, w_res)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# K-tiling edge cases (block_k < K, accumulation across k-steps)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "M,P,R,N,bk",
    [
        (32, 100, 56, 48, 16),  # block_k divides neither P nor R
        (17, 64, 0, 33, 16),  # R == 0, tiled pairs only
        (17, 0, 96, 33, 32),  # P == 0, tiled residual only
        (64, 8, 200, 24, 64),  # bk > P but bk < R (per-segment clamping)
        (5, 3, 2, 7, 2),  # tiny everything, nothing tile-aligned
    ],
)
def test_block_k_tiling_matches_ref(M, P, R, N, bk):
    rng = np.random.default_rng(M * 7 + bk)
    x, kmat, w_res = _rand_case(rng, M, P, R, N, jnp.float32)
    got = paired_matmul(x, kmat, w_res, block_m=16, block_n=16, block_k=bk)
    want = paired_matmul_ref(x, kmat, w_res)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


def test_large_k_8192_within_1e5():
    """Acceptance bar: K up to 8192 with block_k < K, ≤1e-5 vs dense ref."""
    M, N, K = 8, 128, 8192
    P, R = 3000, K - 6000
    rng = np.random.default_rng(11)
    x, kmat, w_res = _rand_case(rng, M, P, R, N, jnp.float32)
    got = np.asarray(paired_matmul(x, kmat, w_res, block_m=8, block_n=128, block_k=512))
    want = np.asarray(paired_matmul_ref(x, kmat, w_res))
    rel = np.abs(got - want).max() / np.abs(want).max()
    assert rel <= 1e-5, f"relative error {rel:.2e} > 1e-5"


def test_bf16_inputs_fp32_accumulation():
    """bf16 in, fp32 accumulate: the tiled kernel must not accumulate in
    bf16 — at K=2048 a bf16 accumulator would be off by ~1e-1."""
    M, P, R, N = 16, 768, 512, 64
    rng = np.random.default_rng(21)
    x, kmat, w_res = _rand_case(rng, M, P, R, N, jnp.bfloat16)
    got = np.asarray(
        paired_matmul(x, kmat, w_res, block_m=16, block_n=32, block_k=128), np.float32
    )
    # fp32 oracle on the bf16-rounded inputs (bit-exact input semantics)
    want = np.asarray(paired_matmul_ref(x, kmat, w_res), np.float32)
    np.testing.assert_allclose(got, want, rtol=5e-2, atol=5e-2)
    # and the kernel is *closer* to the full-fp32 answer than a bf16
    # accumulator could be
    full = np.asarray(x, np.float32)
    want_f32 = (full[:, :P] - full[:, P : 2 * P]) @ np.asarray(kmat, np.float32)
    want_f32 += full[:, 2 * P :] @ np.asarray(w_res, np.float32)
    assert np.abs(got - want_f32).max() / np.abs(want_f32).max() < 2e-2


def test_epilogue_bias_and_activation():
    """Fused bias+activation == reference epilogue applied after the GEMM."""
    M, P, R, N = 40, 32, 32, 24
    rng = np.random.default_rng(31)
    x, kmat, w_res = _rand_case(rng, M, P, R, N, jnp.float32)
    bias = jnp.asarray(rng.normal(size=(N,)), jnp.float32)
    for act, fn in [("none", lambda y: y), ("relu", jax.nn.relu),
                    ("gelu", jax.nn.gelu), ("silu", jax.nn.silu)]:
        got = paired_matmul(
            x, kmat, w_res, bias, block_m=16, block_n=16, block_k=8, activation=act
        )
        want = fn(paired_matmul_ref(x, kmat, w_res) + bias)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4,
            err_msg=f"activation={act}",
        )


# ---------------------------------------------------------------------------
# residual-add epilogue (the fused skip connection)
# ---------------------------------------------------------------------------


def test_residual_epilogue_matches_reference():
    """Fused residual == act(GEMM + bias) + residual, added after the
    activation on the fp32 accumulator."""
    M, P, R, N = 24, 16, 16, 20
    rng = np.random.default_rng(71)
    x, kmat, w_res = _rand_case(rng, M, P, R, N, jnp.float32)
    bias = jnp.asarray(rng.normal(size=(N,)), jnp.float32)
    res = jnp.asarray(rng.normal(size=(M, N)), jnp.float32)
    for act, fn in [("none", lambda y: y), ("relu", jax.nn.relu)]:
        got = paired_matmul(
            x, kmat, w_res, bias, res,
            block_m=16, block_n=16, block_k=8, activation=act,
        )
        want = fn(paired_matmul_ref(x, kmat, w_res) + bias) + res
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4,
            err_msg=f"activation={act}",
        )


def test_residual_none_is_backcompat():
    """residual=None must be byte-identical to omitting the argument."""
    rng = np.random.default_rng(72)
    x, kmat, w_res = _rand_case(rng, 10, 8, 8, 12, jnp.float32)
    b = jnp.asarray(rng.normal(size=(12,)), jnp.float32)
    a = paired_matmul(x, kmat, w_res, b, block_m=8, block_n=8)
    c = paired_matmul(x, kmat, w_res, b, None, block_m=8, block_n=8)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


def test_residual_dtype_promotion_bf16():
    """bf16 residual against fp32 activations (and vice versa): the add
    happens on the fp32 accumulator, then one cast to the output dtype."""
    M, P, R, N = 12, 32, 16, 24
    # fp32 accumulation over P + R lanes in a different order than the
    # oracle's: each lane may round once
    tol32 = (P + R) * float(np.finfo(np.float32).eps)
    rng = np.random.default_rng(73)
    # bf16 residual, fp32 GEMM: promoted exactly (bf16 ⊂ fp32)
    x, kmat, w_res = _rand_case(rng, M, P, R, N, jnp.float32)
    res16 = jnp.asarray(rng.normal(size=(M, N)), jnp.bfloat16)
    got = paired_matmul(x, kmat, w_res, None, res16, block_m=8, block_n=8)
    want = paired_matmul_ref(x, kmat, w_res) + res16.astype(jnp.float32)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol32, atol=tol32)
    # bf16 GEMM, fp32 residual: the accumulator sees the full-precision
    # residual; only the final cast rounds to bf16
    xb, kb, wb = _rand_case(rng, M, P, R, N, jnp.bfloat16)
    res32 = jnp.asarray(rng.normal(size=(M, N)), jnp.float32)
    got_b = paired_matmul(xb, kb, wb, None, res32, block_m=8, block_n=8)
    want_b = (
        np.asarray(paired_matmul_ref(xb, kb, wb), np.float32)
        + np.asarray(res32)
    )
    assert got_b.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got_b, np.float32), want_b, rtol=5e-2, atol=5e-2
    )


def test_blocked_residual_parity():
    """Column-blocked kernel with a fused residual == x @ fold() + res."""
    from repro.core.pairing import pair_rows_blocked
    from repro.kernels.ops import paired_matmul_blocked

    rng = np.random.default_rng(74)
    half = rng.normal(size=(20, 12)) + 1.5
    W = np.concatenate([half, -half + rng.normal(size=(20, 12)) * 0.05])
    x = jnp.asarray(rng.normal(size=(9, 40)), jnp.float32)
    res = jnp.asarray(rng.normal(size=(9, 12)), jnp.float32)
    for block_n in (1, 4, 12):
        bp = pair_rows_blocked(W, 0.5, block_n)
        idx = bp.index_arrays()
        xg = jnp.moveaxis(jnp.take(x, jnp.asarray(idx["perm"]), axis=-1), 1, 0)
        kmat, w_res = bp.packed_weights()
        got = paired_matmul_blocked(
            xg, jnp.asarray(kmat, jnp.float32), jnp.asarray(w_res, jnp.float32),
            None, res, n_cols=12, block_m=8, block_k=16,
        )
        want = x @ jnp.asarray(bp.fold(), jnp.float32) + res
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4,
            err_msg=f"block_n={block_n}",
        )


@cases(10, M=integers(1, 24), P=integers(0, 16), R=integers(0, 16),
       N=integers(1, 24), seed=seeds())
def test_residual_epilogue_property(M, P, R, N, seed):
    """Property: fused residual == ref + residual across random shapes,
    including the degenerate P == 0 / R == 0 segments."""
    if P + R == 0:
        R = 1
    rng = np.random.default_rng(seed)
    x, kmat, w_res = _rand_case(rng, M, P, R, N, jnp.float32)
    res = jnp.asarray(rng.normal(size=(M, N)), jnp.float32)
    got = paired_matmul(x, kmat, w_res, None, res, block_m=16, block_n=16)
    want = paired_matmul_ref(x, kmat, w_res) + res
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_residual_tuning_key_and_vmem():
    """The residual stream is part of the problem identity: the cache key
    gains a -res suffix (back-compat for existing entries) and the VMEM
    model charges the extra output-shaped stream."""
    from repro.kernels.tuning import cache_key, kernel_vmem_bytes

    plain = cache_key(64, 128, 16, 32)
    withres = cache_key(64, 128, 16, 32, residual=True)
    assert withres == plain + "-res"
    assert kernel_vmem_bytes(64, 64, 128, residual=True) > kernel_vmem_bytes(
        64, 64, 128, residual=False
    )


def test_dense_epilogue_matches_xla():
    rng = np.random.default_rng(41)
    x = jnp.asarray(rng.normal(size=(33, 130)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(130, 70)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(70,)), jnp.float32)
    got = dense_matmul(x, w, b, block_m=16, block_n=32, block_k=64, activation="silu")
    want = jax.nn.silu(x @ w + b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


def test_layers_dense_policy_dispatch():
    """layers.dense under a pallas_gemm policy == its XLA einsum path."""
    from repro.models.layers import dense

    rng = np.random.default_rng(51)
    x = jnp.asarray(rng.normal(size=(3, 9, 64)), jnp.float32)  # (B, S, d)
    w = jnp.asarray(rng.normal(size=(64, 48)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(48,)), jnp.float32)
    want = dense(x, w, b, act="gelu")
    with pallas_gemm(block_m=16, block_n=16, block_k=16):
        got = dense(x, w, b, act="gelu")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


def test_layers_dense_policy_gradients():
    """jax.grad through layers.dense under the policy (the train-step path):
    fused Pallas forward must carry a custom VJP whose grads match XLA."""
    from repro.models.layers import dense

    rng = np.random.default_rng(61)
    x = jnp.asarray(rng.normal(size=(2, 5, 32)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(32, 24)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(24,)), jnp.float32)

    def loss(w, b, use_pallas):
        if use_pallas:
            with pallas_gemm(block_m=8, block_n=8, block_k=16):
                y = dense(x, w, b, act="silu")
        else:
            y = dense(x, w, b, act="silu")
        return (y * y).sum()

    gw_ref, gb_ref = jax.grad(loss, argnums=(0, 1))(w, b, False)
    gw, gb = jax.grad(loss, argnums=(0, 1))(w, b, True)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(gw_ref), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gb), np.asarray(gb_ref), rtol=1e-4, atol=1e-4)


def test_tuning_heuristic_fits_vmem():
    from repro.kernels.tuning import VMEM_BUDGET_BYTES, kernel_vmem_bytes

    for M, N, P, R in [(1, 128, 0, 400), (4096, 12288, 3000, 6288),
                       (128, 28672, 0, 12288), (256, 128, 6144, 0)]:
        t = choose_blocks(M, N, P, R)
        assert t.block_k >= 1 and t.block_m >= 1 and t.block_n >= 1
        assert (
            kernel_vmem_bytes(
                t.block_m, t.block_n, t.block_k,
                has_pairs=P > 0, has_resid=R > 0,
            )
            <= VMEM_BUDGET_BYTES
        ), f"heuristic overflows VMEM for {(M, N, P, R)}: {t}"


# ---------------------------------------------------------------------------
# structured-pairing integration (unchanged semantics)
# ---------------------------------------------------------------------------


def test_structured_pairing_end_to_end():
    """paired kernel through a real StructuredPairing == x @ fold()."""
    rng = np.random.default_rng(42)
    # a weight matrix with genuine antisymmetric structure (plus noise small
    # enough for the rms criterion): rows 48.. are ≈ -rows ..48
    half = rng.normal(size=(48, 64)) + 1.5
    W = np.concatenate([half, -half + rng.normal(size=(48, 64)) * 0.05])
    sp = pair_rows_structured(W, rounding=0.5)
    assert sp.n_pairs > 0, "want a nontrivial pairing for this test"
    x = jnp.asarray(rng.normal(size=(10, 96)), jnp.float32)
    y_kernel = apply_structured_pairing(x, sp, block_m=16, block_n=16, block_k=16)
    y_dense = x @ jnp.asarray(sp.fold(), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(y_kernel), np.asarray(y_dense), rtol=1e-4, atol=1e-4
    )


def test_blocked_pairing_end_to_end():
    """blocked kernel through a real BlockedPairing == x @ fold(), and at
    block_n = N it agrees with the structured kernel path."""
    from repro.core.pairing import pair_rows_blocked
    from repro.kernels.ops import apply_blocked_pairing

    rng = np.random.default_rng(43)
    half = rng.normal(size=(24, 20)) + 1.5
    W = np.concatenate([half, -half + rng.normal(size=(24, 20)) * 0.05])
    x = jnp.asarray(rng.normal(size=(3, 9, 48)), jnp.float32)  # lead dims
    for block_n in (1, 5, 20):
        bp = pair_rows_blocked(W, 0.5, block_n)
        assert bp.n_pairs > 0, "want a nontrivial pairing for this test"
        y_kernel = apply_blocked_pairing(x, bp, block_m=8, block_k=16)
        y_dense = x @ jnp.asarray(bp.fold(), jnp.float32)
        assert y_kernel.shape == y_dense.shape == (3, 9, 20)
        np.testing.assert_allclose(
            np.asarray(y_kernel), np.asarray(y_dense), rtol=1e-4, atol=1e-4
        )
    # the single-block case is the structured pairing, kernel included
    bpN = pair_rows_blocked(W, 0.5, 20)
    spN = pair_rows_structured(W, 0.5)
    np.testing.assert_allclose(
        np.asarray(apply_blocked_pairing(x, bpN, block_m=8, block_k=16)),
        np.asarray(apply_structured_pairing(
            x, spN, block_m=8, block_n=8, block_k=16
        )),
        rtol=1e-4, atol=1e-4,
    )


def test_contraction_savings_accounting():
    """The kernel's MXU contraction length is K - P: every pair saves a lane."""
    rng = np.random.default_rng(1)
    W = np.concatenate([rng.normal(size=(32, 16)) + 2, -(rng.normal(size=(32, 16)) + 2)])
    sp = pair_rows_structured(W, rounding=10.0)  # everything pairs
    K = W.shape[0]
    assert sp.n_pairs == 32
    assert sp.Kmat.shape[0] + sp.W_res.shape[0] == K - sp.n_pairs


def test_batched_inputs():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(4, 7, 48)), jnp.float32)  # (B, S, K)
    kmat = jnp.asarray(rng.normal(size=(16, 24)), jnp.float32)
    w_res = jnp.asarray(rng.normal(size=(16, 24)), jnp.float32)
    got = paired_matmul(x, kmat, w_res, block_m=8, block_n=8)
    assert got.shape == (4, 7, 24)
    want = paired_matmul_ref(x.reshape(-1, 48), kmat, w_res).reshape(4, 7, 24)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)
