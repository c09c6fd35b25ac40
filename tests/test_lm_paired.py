"""Paired subtractor GEMMs on the LM decode path.

r=0 parity (≤1e-5 vs the XLA einsum path) for every paired decoder GEMM —
attention qkv, the out-projection (including its fused residual-add
epilogue) and the MLP up/gate/down — on a tiny fp32 decoder config, under
jit and jax.grad; at r > 0 the kernel matches the folded oracle and the
deviation from the exact GEMM obeys the analytic rms bound from
test_pairing.  Both pairing-spectrum endpoints are exercised: structured
(shared-row) and per-column (block_n=1) metadata.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.pairing import pair_rows_structured
from repro.core.transform import (
    LM_PAIRED_WEIGHTS,
    _stack_structured,
    has_lm_pairing,
    pair_lm_params,
)
from repro.kernels.ops import (
    fold_lm_params,
    fold_lm_weight,
    fused_paired_dense,
    pallas_paired_gemm,
    perf_context,
)
from repro.models import lm as M
from repro.models.param import unzip


@pytest.fixture(scope="module")
def tiny_lm():
    """fp32 qwen2-family smoke decoder + params (fp32: parity is exactness
    of the kernel path, not bf16 rounding noise)."""
    cfg = dataclasses.replace(get_smoke_config("qwen2-1.5b"), dtype="float32")
    params, _ = unzip(M.init_lm(cfg, jax.random.key(0)))
    return cfg, params


def _layer_weight_matrices(params):
    """{(sub, name): (K, N) jnp matrix} for layer 0 of segment 0."""
    seg = params["segments"][0]
    out = {}
    for sub, name in LM_PAIRED_WEIGHTS:
        if sub not in seg or name not in seg[sub]:
            continue
        w = jnp.asarray(seg[sub][name][0], jnp.float32)  # layer 0
        out[(sub, name)] = w.reshape(-1, w.shape[-1]) if name == "wo" else w.reshape(w.shape[0], -1)
    return out


def _structured_meta(w2: np.ndarray, rounding: float) -> dict:
    """Single-layer structured metadata in the stacked-artifact layout."""
    sp = pair_rows_structured(np.asarray(w2, np.float64), rounding)
    stacked = _stack_structured([sp])
    return {k: jnp.asarray(v[0]) for k, v in stacked.items()}


# ---------------------------------------------------------------------------
# GEMM-level r=0 parity: every paired decoder weight, jit + grad
# ---------------------------------------------------------------------------


def test_each_decoder_gemm_r0_parity(tiny_lm):
    """fused_paired_dense at rounding 0 == x @ W ≤ 1e-5 for qkv/wo/MLP."""
    _, params = tiny_lm
    mats = _layer_weight_matrices(params)
    assert len(mats) == 7, sorted(mats)  # wq wk wv wo + gate/up/down
    rng = np.random.default_rng(0)
    for (sub, name), w2 in mats.items():
        meta = _structured_meta(np.asarray(w2), 0.0)
        x = jnp.asarray(rng.normal(size=(3, w2.shape[0])), jnp.float32)
        got = np.asarray(fused_paired_dense(x, w2, meta, block_m=8, block_n=8))
        want = np.asarray(x @ w2)
        rel = np.abs(got - want).max() / max(np.abs(want).max(), 1e-30)
        assert rel <= 1e-5, f"{sub}.{name}: rel err {rel:.2e}"


def test_fused_paired_dense_under_jit_and_grad(tiny_lm):
    """jit(fused_paired_dense) and its custom VJP match the XLA dense path
    at rounding 0 (the folded equivalent IS the original weight there)."""
    _, params = tiny_lm
    w2 = _layer_weight_matrices(params)[("mlp", "w_down")]
    meta = _structured_meta(np.asarray(w2), 0.0)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(2, 4, w2.shape[0])), jnp.float32)
    res = jnp.asarray(rng.normal(size=(2, 4, w2.shape[1])), jnp.float32)

    got = jax.jit(
        lambda x, w: fused_paired_dense(
            x, w, meta, activation="silu", residual=res, block_m=8, block_n=8
        )
    )(x, w2)
    want = jax.nn.silu(jnp.einsum("...d,df->...f", x, w2)) + res
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)

    def loss(w, kernel):
        if kernel:
            y = fused_paired_dense(x, w, meta, activation="silu",
                                   residual=res, block_m=8, block_n=8)
        else:
            y = jax.nn.silu(jnp.einsum("...d,df->...f", x, w)) + res
        return (y * y).sum()

    g_k = jax.grad(loss)(w2, True)
    g_x = jax.grad(loss)(w2, False)
    np.testing.assert_allclose(np.asarray(g_k), np.asarray(g_x),
                               rtol=1e-4, atol=1e-4)


def test_fused_residual_epilogue_vs_explicit_add(tiny_lm):
    """The residual-add epilogue == the explicit x @ W + res schedule."""
    _, params = tiny_lm
    w2 = _layer_weight_matrices(params)[("attn", "wo")]
    meta = _structured_meta(np.asarray(w2), 0.0)
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(5, w2.shape[0])), jnp.float32)
    res = jnp.asarray(rng.normal(size=(5, w2.shape[1])), jnp.float32)
    fused = fused_paired_dense(x, w2, meta, residual=res, block_m=8, block_n=8)
    explicit = fused_paired_dense(x, w2, meta, block_m=8, block_n=8) + res
    np.testing.assert_allclose(np.asarray(fused), np.asarray(explicit),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# r > 0: folded-oracle parity + the analytic rms error bound
# ---------------------------------------------------------------------------


def _pairable_matrix(rng, K, N, rounding):
    """Rows K/2.. ≈ −rows ..K/2 with sub-rounding noise → pairs are found."""
    half = rng.normal(size=(K // 2, N)) + 1.5
    noise = rng.normal(size=(K // 2, N)) * (rounding * 0.1)
    return np.concatenate([half, -half + noise])


@pytest.mark.parametrize("mode,block_n", [("structured", 0), ("per_column", 1)])
def test_positive_rounding_holds_rms_bound(mode, block_n):
    """At r > 0: kernel == folded oracle ≤ 1e-4, and the deviation from the
    exact GEMM obeys 2·max|x|·P·√N·r (the test_pairing rms bound, lifted
    through the contraction)."""
    rounding = 0.1
    K, N = 32, 12
    rng = np.random.default_rng(3)
    W = _pairable_matrix(rng, K, N, rounding)
    w2 = jnp.asarray(W, jnp.float32)
    x = jnp.asarray(rng.normal(size=(6, K)), jnp.float32)

    if mode == "structured":
        meta = _structured_meta(W, rounding)
        n_pairs = int(meta["pair_mask"].sum())
        got = fused_paired_dense(x, w2, meta, block_m=8, block_n=8)
        wf = fold_lm_weight(w2, meta)
    else:
        fake = {"segments": [{"mlp": {"w_down": W[None]}}]}
        pm, rep = pair_lm_params(fake, rounding, mode="per_column")
        meta = {k: jnp.asarray(v[0])
                for k, v in pm["segments"][0]["mlp"]["w_down_pairing"].items()}
        n_pairs = rep.total_pairs // N  # weighted → per-column average ≥ 1
        got = fused_paired_dense(x, w2, meta, pair_block_n=1, block_m=8)
        wf = fold_lm_weight(w2, meta, pair_block_n=1)
    assert n_pairs > 0, "want a nontrivial pairing for this test"

    oracle = jnp.einsum("...d,df->...f", x, wf)
    np.testing.assert_allclose(np.asarray(got), np.asarray(oracle),
                               rtol=1e-4, atol=1e-4)
    exact = np.asarray(x @ w2)
    err = np.abs(np.asarray(got) - exact).max()
    bound = 2 * float(jnp.abs(x).max()) * (K // 2) * np.sqrt(N) * rounding
    assert err <= bound, f"error {err:.3e} exceeds analytic bound {bound:.3e}"


# ---------------------------------------------------------------------------
# model-level: lm_forward / lm_loss under the policy, structured + blocked
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode,block_n", [("structured", 0), ("per_column", 1)])
def test_lm_forward_r0_parity(tiny_lm, mode, block_n):
    """Full decoder forward through the paired kernel at rounding 0 matches
    the XLA path ≤ 1e-5 (jit'd, both pairing-spectrum endpoints)."""
    cfg, params = tiny_lm
    knobs = M.PerfKnobs(q_chunk=16, k_chunk=16, remat="none",
                        gemm="pallas_paired", pair_block_n=block_n)
    pm, rep = pair_lm_params(params, 0.0, mode=mode, block_n=block_n)
    assert has_lm_pairing(pm) and not has_lm_pairing(params)
    assert len(rep.leaves) == 7

    batch = {"tokens": jnp.asarray(
        np.random.default_rng(4).integers(0, cfg.vocab, (2, 8)), jnp.int32)}
    want, _, _ = M.lm_forward(cfg, params, batch, knobs=M.PerfKnobs(
        q_chunk=16, k_chunk=16, remat="none"))
    with perf_context(knobs):
        got, _, _ = jax.jit(
            lambda p: M.lm_forward(cfg, p, batch, knobs=knobs)
        )(pm)
    rel = float(jnp.abs(got - want).max() / jnp.abs(want).max())
    assert rel <= 1e-5, f"{mode}: rel err {rel:.2e}"


def test_lm_loss_grad_r0_parity(tiny_lm):
    """jax.grad through lm_loss under the paired policy (scan + custom VJP):
    weight gradients match the XLA path — the artifact survives training."""
    cfg, params = tiny_lm
    pm, _ = pair_lm_params(params, 0.0)
    rng = np.random.default_rng(5)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab, (1, 6)), jnp.int32),
        "labels": jnp.asarray(rng.integers(0, cfg.vocab, (1, 6)), jnp.int32),
    }
    base = M.PerfKnobs(q_chunk=8, k_chunk=8, remat="none", xent_chunk=0)
    knobs = dataclasses.replace(base, gemm="pallas_paired")

    def loss_xla(p):
        return M.lm_loss(cfg, p, batch, knobs=base)[0]

    def loss_paired(p):
        with pallas_paired_gemm():
            return M.lm_loss(cfg, p, batch, knobs=knobs)[0]

    g_ref = jax.grad(loss_xla)(params)
    # allow_int: the pairing metadata (int32 lane indices) rides inside the
    # param tree; its cotangents are float0 (the structure is frozen)
    g_got = jax.grad(loss_paired, allow_int=True)(pm)
    for sub, name in LM_PAIRED_WEIGHTS:
        ref = np.asarray(g_ref["segments"][0][sub][name])
        got = np.asarray(g_got["segments"][0][sub][name])
        np.testing.assert_allclose(
            got, ref, rtol=1e-4, atol=1e-5,
            err_msg=f"grad mismatch on segments[0].{sub}.{name}",
        )


def test_decode_step_r0_parity(tiny_lm):
    """prefill → decode_step through the paired kernel == XLA, per logit."""
    cfg, params = tiny_lm
    pm, _ = pair_lm_params(params, 0.0)
    base = M.PerfKnobs(q_chunk=16, k_chunk=16, remat="none")
    knobs = dataclasses.replace(base, gemm="pallas_paired")
    batch = {"tokens": jnp.asarray(
        np.random.default_rng(6).integers(0, cfg.vocab, (2, 7)), jnp.int32)}
    tok = jnp.asarray([[3], [9]], jnp.int32)
    pos = jnp.asarray([7, 7], jnp.int32)

    _, cache = M.prefill(cfg, params, batch, knobs=base)
    want, _ = M.decode_step(cfg, params, cache, tok, pos)
    with perf_context(knobs):
        _, cache_p = M.prefill(cfg, pm, batch, knobs=knobs)
        got, _ = jax.jit(
            lambda p, c: M.decode_step(cfg, p, c, tok, pos)
        )(pm, cache_p)
    rel = float(jnp.abs(got - want).max() / jnp.abs(want).max())
    assert rel <= 1e-5, f"decode rel err {rel:.2e}"


@pytest.mark.parametrize("mode,block_n", [("structured", 0), ("per_column", 1)])
def test_fold_lm_params_is_the_xla_reference_at_r_gt_0(tiny_lm, mode, block_n):
    """fold_lm_params drops the metadata and folds every paired weight: the
    XLA forward on the folded tree is what the paired kernels compute."""
    cfg, params = tiny_lm
    pm, _ = pair_lm_params(params, 0.3, mode=mode, block_n=block_n)
    folded = fold_lm_params(pm, block_n)
    assert not has_lm_pairing(folded)
    assert jax.tree.structure(folded) == jax.tree.structure(params)
    changed = [
        name for sub, name in LM_PAIRED_WEIGHTS
        if not np.allclose(folded["segments"][0][sub][name],
                           params["segments"][0][sub][name])
    ]
    assert changed, "rounding 0.3 must snap some pair magnitudes"
    base = M.PerfKnobs(q_chunk=16, k_chunk=16, remat="none")
    knobs = dataclasses.replace(base, gemm="pallas_paired", pair_block_n=block_n)
    batch = {"tokens": jnp.asarray(
        np.random.default_rng(7).integers(0, cfg.vocab, (2, 8)), jnp.int32)}
    want, _, _ = M.lm_forward(cfg, folded, batch, knobs=base)
    with perf_context(knobs):
        got, _, _ = jax.jit(lambda p: M.lm_forward(cfg, p, batch, knobs=knobs))(pm)
    rel = float(jnp.abs(got - want).max() / jnp.abs(want).max())
    assert rel <= 1e-5, f"{mode}: rel err {rel:.2e}"
