"""Public jit'd wrappers around the Pallas kernels.

On a real TPU backend ``interpret=False`` compiles the Mosaic kernel; in this
CPU container the kernels run (and are tested) in interpret mode.  The
wrapper also owns the *deployment* plumbing:

* applying a :class:`repro.core.pairing.StructuredPairing` to activations,
  including the input permutation (which in production folds into the
  previous layer);
* resolving tile sizes — pass ``block_* = 0`` and the heuristic in
  :mod:`repro.kernels.tuning` picks VMEM-safe tiles for the shape;
* the **GEMM policy**: :func:`pallas_gemm` installs a thread-local policy
  that makes :func:`repro.models.layers.dense` (and everything built on it —
  MLP blocks, the serving engine, the pjit'd step builders) route its
  matmuls through the fused kernels instead of XLA einsums.  Activating the
  policy around a ``jax.jit`` trace bakes the kernels into the compiled
  step.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.pairing import BlockedPairing, StructuredPairing
from repro.kernels import tuning
from repro.kernels.paired_matmul import (
    dense_matmul_pallas,
    paired_matmul_blocked_pallas,
    paired_matmul_pallas,
)
from repro.parallel.sharding import current_mesh


def on_mesh(launch):
    """``launch`` (a kernel call) as the active mesh can run it.

    XLA cannot partition a compiled Mosaic kernel, so inside a sharded step
    the kernel runs whole on every device of the mesh, its operands gathered
    (a ``shard_map`` over replicated specs): correct, not yet sharded.  The
    interpreted kernel takes the same path, so a CPU mesh runs the program
    a TPU mesh runs.
    """
    mesh = current_mesh()
    if mesh is None or mesh.size == 1:
        return launch
    P = jax.sharding.PartitionSpec
    return jax.shard_map(launch, mesh=mesh, in_specs=P(), out_specs=P(),
                         check_vma=False)


@functools.partial(
    jax.jit,
    static_argnames=(
        "block_m", "block_n", "block_k", "activation", "pool", "interpret",
    ),
)
def paired_matmul(
    x: jax.Array,
    kmat: jax.Array,
    w_res: jax.Array,
    bias: jax.Array | None = None,
    residual: jax.Array | None = None,
    *,
    block_m: int = 0,
    block_n: int = 0,
    block_k: int = 0,
    activation: str = "none",
    pool: str = "none",
    interpret: bool | None = None,
) -> jax.Array:
    """(…, K) @ paired weights → (…, N). x pre-permuted to [I|J|residual].

    ``block_* = 0`` → tiles from :mod:`repro.kernels.tuning` (a warm
    :class:`~repro.kernels.tuning.TileCache` hit wins over the heuristic).
    ``bias``/``activation`` fuse into the kernel epilogue, and ``residual``
    (an output-shaped ``(…, N)`` skip connection) fuses into the flush
    after them.  With ``pool="max2"``/``"avg2"`` ``x`` must be window-major
    ``(4, M, K)`` and the fused 2×2 reduction happens in VMEM (see
    paired_matmul_pallas); ``residual`` is then the pooled ``(M, N)`` map.
    """
    has_pool = pool != "none"
    if has_pool:
        assert x.ndim == 3, f"pool={pool!r} expects (4, M, K) x, got {x.shape}"
        lead, x2 = (), x
    else:
        lead = x.shape[:-1]
        x2 = x.reshape(-1, x.shape[-1])
    res2 = None
    if residual is not None:
        res2 = residual.reshape(-1, residual.shape[-1])
    tiles = tuning.resolve_blocks(
        x2.shape[-2], kmat.shape[1], kmat.shape[0], w_res.shape[0],
        block_m=block_m, block_n=block_n, block_k=block_k,
        dtype_bytes=x.dtype.itemsize, dtype=x.dtype.name, pool=pool,
        residual=residual is not None,
    )
    y = paired_matmul_pallas(
        x2, kmat, w_res, bias, residual=res2,
        block_m=tiles.block_m, block_n=tiles.block_n, block_k=tiles.block_k,
        activation=activation, pool=pool, interpret=interpret,
    )
    # pooled output is already (M_pooled, N); otherwise restore the lead dims
    # (incl. the 1-D (K,) → (N,) case, where lead == ())
    return y if has_pool else y.reshape(*lead, y.shape[-1])


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "activation", "interpret"),
)
def dense_matmul(
    x: jax.Array,
    w: jax.Array,
    bias: jax.Array | None = None,
    residual: jax.Array | None = None,
    *,
    block_m: int = 0,
    block_n: int = 0,
    block_k: int = 0,
    activation: str = "none",
    interpret: bool | None = None,
) -> jax.Array:
    """Plain K-tiled GEMM with the same tiling/epilogue as the paired kernel."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    res2 = None if residual is None else residual.reshape(-1, residual.shape[-1])
    tiles = tuning.resolve_blocks(
        x2.shape[0], w.shape[1], 0, w.shape[0],
        block_m=block_m, block_n=block_n, block_k=block_k,
        dtype_bytes=x.dtype.itemsize, dtype=x.dtype.name,
        residual=residual is not None,
    )
    y = dense_matmul_pallas(
        x2, w, bias, residual=res2,
        block_m=tiles.block_m, block_n=tiles.block_n, block_k=tiles.block_k,
        activation=activation, interpret=interpret,
    )
    return y.reshape(*lead, y.shape[-1])


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_cols", "block_m", "block_k", "activation", "pool", "interpret",
    ),
)
def paired_matmul_blocked(
    x: jax.Array,
    kmat: jax.Array,
    w_res: jax.Array,
    bias: jax.Array | None = None,
    residual: jax.Array | None = None,
    *,
    n_cols: int,
    block_m: int = 0,
    block_k: int = 0,
    activation: str = "none",
    pool: str = "none",
    interpret: bool | None = None,
) -> jax.Array:
    """Column-blocked paired GEMM → (M, n_cols).

    ``x`` is block-gathered ``(B, M, K')`` (window-major ``(B, 4, M, K')``
    with pooling), ``kmat``/``w_res`` the packed per-block weight segments —
    see :func:`repro.kernels.paired_matmul.paired_matmul_blocked_pallas`.
    ``residual`` is an output-space ``(M, n_cols)`` fused skip connection.
    The lane tile is pinned to the pairing block size; ``block_m``/
    ``block_k = 0`` resolve through the tile cache / heuristic under a
    blocked cache key.
    """
    B, P, bn = kmat.shape
    R = w_res.shape[1]
    tiles = tuning.resolve_blocks(
        x.shape[-2], bn, P, R,
        block_m=block_m, block_n=bn, block_k=block_k,
        dtype_bytes=x.dtype.itemsize, dtype=x.dtype.name, pool=pool,
        blocks=B, residual=residual is not None,
    )
    return paired_matmul_blocked_pallas(
        x, kmat, w_res, bias, residual=residual,
        n_cols=n_cols, block_m=tiles.block_m, block_k=tiles.block_k,
        activation=activation, pool=pool, interpret=interpret,
    )


def apply_blocked_pairing(
    x: jax.Array, bp: BlockedPairing, **kw
) -> jax.Array:
    """Evaluate x @ W through the blocked kernel given a BlockedPairing.

    The blocked analogue of :func:`apply_structured_pairing`: gathers the
    activations through the packed ``(n_blocks, K')`` index matrix (one XLA
    gather covering every block's ``[I | J | resid]`` permutation) and packs
    the offline per-block weight segments.  For the live-weight
    (differentiable) variant see ``kernels.paired_conv``.
    """
    lead = x.shape[:-1]
    idx = bp.index_arrays()
    xg = jnp.take(x.reshape(-1, x.shape[-1]), jnp.asarray(idx["perm"]), axis=-1)
    xg = jnp.moveaxis(xg, 1, 0)  # (B, M, K')
    kmat, w_res = bp.packed_weights()
    y = paired_matmul_blocked(
        xg, jnp.asarray(kmat, x.dtype), jnp.asarray(w_res, x.dtype),
        n_cols=bp.shape[1], **kw,
    )
    return y.reshape(*lead, y.shape[-1])


def apply_structured_pairing(
    x: jax.Array, sp: StructuredPairing, *, fold_perm: bool = False, **kw
) -> jax.Array:
    """Evaluate x @ W through the paired kernel given a StructuredPairing.

    ``fold_perm=False`` applies the [I|J|residual] permutation here (one
    gather); in production the permutation folds into the previous layer's
    output weights and the gather disappears.
    """
    perm = jnp.asarray(sp.perm())
    xp = x if fold_perm else jnp.take(x, perm, axis=-1)
    kmat = jnp.asarray(sp.Kmat, dtype=x.dtype)
    w_res = jnp.asarray(sp.W_res, dtype=x.dtype)
    return paired_matmul(xp, kmat, w_res, **kw)


# ---------------------------------------------------------------------------
# differentiable fused dense: Pallas forward, XLA backward
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _fused_dense_grad(activation, block_m, block_n, block_k, interpret):
    """custom_vjp wrapper: forward through the fused kernel, backward as
    plain XLA dots (pallas_call has no transpose rule; the backward of a
    GEMM is two GEMMs XLA already schedules well, with the pre-activation
    rematerialised — standard remat trade)."""
    from repro.kernels.paired_matmul import ACTIVATIONS

    def primal(x, w, b):
        return on_mesh(functools.partial(
            dense_matmul, activation=activation,
            block_m=block_m, block_n=block_n, block_k=block_k,
            interpret=interpret,
        ))(x, w, b)

    @jax.custom_vjp
    def f(x, w, b):
        return primal(x, w, b)

    def fwd(x, w, b):
        return primal(x, w, b), (x, w, b)

    def bwd(res, dy):
        x, w, b = res
        z = jnp.einsum("...d,df->...f", x, w)
        if b is not None:
            z = z + b
        _, act_vjp = jax.vjp(ACTIVATIONS[activation], z)
        (dz,) = act_vjp(dy)
        dx = jnp.einsum("...f,df->...d", dz, w)
        dw = jnp.einsum("...d,...f->df", x, dz)
        db = None if b is None else dz.reshape(-1, dz.shape[-1]).sum(0)
        return dx, dw.astype(w.dtype), db

    f.defvjp(fwd, bwd)
    return f


def fused_dense(
    x: jax.Array,
    w: jax.Array,
    bias: jax.Array | None = None,
    *,
    activation: str = "none",
    block_m: int = 0,
    block_n: int = 0,
    block_k: int = 0,
    interpret: bool | None = None,
) -> jax.Array:
    """Differentiable fused GEMM: what layers.dense calls under the policy."""
    grad_fn = _fused_dense_grad(activation, block_m, block_n, block_k, interpret)
    return grad_fn(x, w, bias)


# ---------------------------------------------------------------------------
# differentiable fused *paired* dense: live-weight subtractor GEMM for the LM
# ---------------------------------------------------------------------------
#
# The LM analogue of kernels.paired_conv: the pairing artifact
# (core.transform.pair_lm_params) carries only the frozen *index structure*
# of which contraction lanes subtract — as stacked arrays with a leading
# layers axis, so a lax.scan over a decoder segment slices each layer's
# metadata like any other scanned operand.  Pair magnitudes are recomputed
# from the live weights inside the trace (Kmat = (W[I] − W[J]) / 2), so the
# same artifact serves inference and jax.grad.  Lane lists are padded to a
# segment-wide (Pmax, Rmax): padded pair lanes point I == J == 0 (the
# subtract is exactly zero) and every padded weight row is masked to zero,
# so padding contracts against nothing — the same zero-lane trick the
# k-tile padding and the column-blocked packing already use.  The functions
# that make those live segments run under the ``refold`` named scope, so a
# profiler trace names the device time of the per-step recomputation.


@jax.named_scope("refold")
def _lm_structured_segments(w2: jax.Array, meta: dict):
    """Live (kmat, w_res) for a structured LM pairing (traced indices)."""
    I, J, Rm = meta["I"], meta["J"], meta["resid"]
    kmat = (jnp.take(w2, I, axis=0) - jnp.take(w2, J, axis=0)) * 0.5
    kmat = kmat * meta["pair_mask"][:, None].astype(w2.dtype)
    w_res = jnp.take(w2, Rm, axis=0) * meta["resid_mask"][:, None].astype(w2.dtype)
    return kmat, w_res


@jax.named_scope("refold")
def _lm_blocked_weights(w2: jax.Array, n_blocks: int, bn: int) -> jax.Array:
    """(K, N) live weights → block-major (n_blocks, K, bn), zero-padded cols."""
    K, N = w2.shape
    pad = n_blocks * bn - N
    w_p = jnp.pad(w2, ((0, 0), (0, pad))) if pad else w2
    return w_p.reshape(K, n_blocks, bn).transpose(1, 0, 2)


@jax.named_scope("refold")
def _take_block_segments(wm_t: jax.Array, meta: dict):
    """Live per-block (kmat, w_res) from block-major (B, K, bn) weights and
    (B, Pmax/Rmax) lane lists."""
    take = lambda ind: jnp.take_along_axis(wm_t, ind[:, :, None], axis=1)
    pmask = meta["pair_mask"][:, :, None].astype(wm_t.dtype)
    rmask = meta["resid_mask"][:, :, None].astype(wm_t.dtype)
    kmat = (take(meta["I"]) - take(meta["J"])) * 0.5 * pmask  # (B, Pmax, bn)
    w_res = take(meta["resid"]) * rmask  # (B, Rmax, bn)
    return kmat, w_res


def _lm_blocked_segments(w2: jax.Array, meta: dict, bn: int):
    """Packed per-block live (kmat, w_res) for a blocked LM pairing."""
    wm_t = _lm_blocked_weights(w2, meta["I"].shape[0], bn)  # (B, K, bn)
    return _take_block_segments(wm_t, meta)


def fold_lm_weight(w2: jax.Array, meta: dict, pair_block_n: int = 0) -> jax.Array:
    """Dense W_approx (K, N) the paired LM GEMM is equivalent to.

    The live-weight fold under a frozen pairing structure (the backward-pass
    function and test oracle): paired rows snap to ±Kmat, residual rows pass
    through.  Scatter-*add* because padded lanes all point at row 0 with
    exactly-zero masked contributions.
    """
    if meta["I"].ndim == 2:  # blocked: (B, Pmax)-shaped lane lists
        B = meta["I"].shape[0]
        K, N = w2.shape
        bn = pair_block_n
        assert bn >= 1 and B == -(-N // bn), (B, N, bn)
        kmat, w_res = _lm_blocked_segments(w2, meta, bn)
        bar = jnp.arange(B)[:, None]
        wf_t = (
            jnp.zeros((B, K, bn), w2.dtype)
            .at[bar, meta["I"]].add(kmat)
            .at[bar, meta["J"]].add(-kmat)
            .at[bar, meta["resid"]].add(w_res)
        )
        return wf_t.transpose(1, 0, 2).reshape(K, B * bn)[:, :N]
    kmat, w_res = _lm_structured_segments(w2, meta)
    return (
        jnp.zeros_like(w2)
        .at[meta["I"]].add(kmat)
        .at[meta["J"]].add(-kmat)
        .at[meta["resid"]].add(w_res)
    )


def fold_lm_params(params, pair_block_n: int = 0):
    """The dense tree a paired LM tree is equivalent to.

    Every stacked decoder weight that carries a ``<name>_pairing`` sibling
    becomes its per-layer :func:`fold_lm_weight` and the metadata goes: the
    plain XLA-path reference that ``gemm="pallas_paired"`` is held to at any
    rounding.  Expert (MoE) weights are not covered.
    """
    from repro.core.transform import _lm_weight_matrix_shape

    def fold(name, w, meta):
        K, N = _lm_weight_matrix_shape(name, w.shape[1:])
        one = lambda w1, m1: fold_lm_weight(w1.reshape(K, N), m1, pair_block_n)
        return jax.vmap(one)(w, meta).reshape(w.shape)

    def walk(node):
        if isinstance(node, list):
            return [walk(x) for x in node]
        if not isinstance(node, dict):
            return node
        if "moe" in node and has_pairing(node["moe"]):
            raise NotImplementedError("fold_lm_params does not fold expert weights")
        return {
            k: fold(k, v, node[k + "_pairing"]) if k + "_pairing" in node else walk(v)
            for k, v in node.items() if not k.endswith("_pairing")
        }

    def has_pairing(node):
        return isinstance(node, dict) and any(k.endswith("_pairing") for k in node)

    return walk(params)


@functools.lru_cache(maxsize=None)
def _fused_paired_dense_grad(
    activation, blocked, pair_block_n, block_m, block_n, block_k, interpret
):
    """custom_vjp factory: forward through the paired kernel (live-weight
    segments, fused bias/activation/residual epilogue), backward as the VJP
    of the folded dense equivalent — the same Pallas-forward / folded-XLA-
    backward split as paired_conv and fused_dense.  The pairing metadata is
    a primal argument (its leaves are traced scan slices), with float0 /
    zero cotangents: only the *structure* is frozen, weights stay live."""
    from repro.kernels.paired_matmul import ACTIVATIONS

    def primal(x, w2, b, res, meta):
        N = w2.shape[1]
        perm = jnp.concatenate([meta["I"], meta["J"], meta["resid"]], axis=-1)
        if blocked:
            x2 = x.reshape(-1, x.shape[-1])
            xg = jnp.moveaxis(jnp.take(x2, perm, axis=-1), -2, 0)  # (B, M, K')
            kmat, w_res = _lm_blocked_segments(w2, meta, pair_block_n)
            res2 = None if res is None else res.reshape(-1, N)
            y = on_mesh(functools.partial(
                paired_matmul_blocked, n_cols=N, activation=activation,
                block_m=block_m, block_k=block_k, interpret=interpret,
            ))(xg, kmat.astype(x.dtype), w_res.astype(x.dtype), b, res2)
            return y.reshape(*x.shape[:-1], N)
        xg = jnp.take(x, perm, axis=-1)
        kmat, w_res = _lm_structured_segments(w2, meta)
        return on_mesh(functools.partial(
            paired_matmul, activation=activation,
            block_m=block_m, block_n=block_n, block_k=block_k,
            interpret=interpret,
        ))(xg, kmat.astype(x.dtype), w_res.astype(x.dtype), b, res)

    def ref(x, w2, b, res, meta):
        wf = fold_lm_weight(w2, meta, pair_block_n)
        z = jnp.einsum("...d,df->...f", x, wf)
        if b is not None:
            z = z + b
        z = ACTIVATIONS[activation](z)
        return z + res.astype(z.dtype) if res is not None else z

    @jax.custom_vjp
    def f(x, w2, b, res, meta):
        return primal(x, w2, b, res, meta)

    def fwd(x, w2, b, res, meta):
        return primal(x, w2, b, res, meta), (x, w2, b, res, meta)

    def bwd(saved, dy):
        x, w2, b, res, meta = saved
        _, vjp = jax.vjp(lambda x, w2, b, res: ref(x, w2, b, res, meta),
                         x, w2, b, res)
        dx, dw, db, dres = vjp(dy)
        dmeta = {
            k: np.zeros(jnp.shape(a), jax.dtypes.float0)
            if jnp.issubdtype(jnp.result_type(a), jnp.integer)
            else jnp.zeros_like(a)
            for k, a in meta.items()
        }
        return dx, dw.astype(w2.dtype), db, dres, dmeta

    f.defvjp(fwd, bwd)
    return f


def fused_paired_dense(
    x: jax.Array,
    w: jax.Array,  # (K, N) live weights (reshape conv/attn weights first)
    meta: dict,  # stacked pairing metadata (core.transform.pair_lm_params)
    bias: jax.Array | None = None,
    *,
    activation: str = "none",
    residual: jax.Array | None = None,
    pair_block_n: int = 0,
    block_m: int = 0,
    block_n: int = 0,
    block_k: int = 0,
    interpret: bool | None = None,
) -> jax.Array:
    """Differentiable paired GEMM from live weights + frozen LM pairing.

    ``meta`` holds the per-layer lane structure (``I``/``J``/``resid`` +
    masks); 1-D lane lists select the structured kernel, 2-D ``(B, Pmax)``
    lists the column-blocked one (``pair_block_n`` is then the pairing
    block size the metadata was built with).  ``residual`` fuses the
    sublayer skip connection into the kernel flush.
    """
    blocked = meta["I"].ndim == 2
    if blocked and pair_block_n < 1:
        raise ValueError("blocked pairing metadata needs pair_block_n >= 1")
    fn = _fused_paired_dense_grad(
        activation, blocked, pair_block_n if blocked else 0,
        block_m, block_n, block_k, interpret,
    )
    return fn(x, w, bias, residual, dict(meta))


# ---------------------------------------------------------------------------
# differentiable fused paired dense over a leading expert axis (MoE)
# ---------------------------------------------------------------------------
#
# Per-expert pairing executes on the *existing* column-blocked Pallas kernel
# by mapping experts onto the kernel's block grid: structured-per-expert
# metadata (E, Pmax) makes each expert one block of bn = F output columns;
# blocked-within-expert metadata (E, Bc, Pmax) flattens to E·Bc blocks of
# pair_block_n columns each.  Either way the result is (M, E, F) — the
# einsum "tk,ekf->tef" (shared activations) or "etk,ekf->tef" (per-expert
# activations; the kernel contracts each expert's token rows against that
# expert's weight segments only, so nothing is wasted on the batching).


@jax.named_scope("refold")
def _expert_blocked_weights(w: jax.Array, n_blocks: int, bn: int) -> jax.Array:
    """(E, K, F) live expert weights → block-major (E·n_blocks, K, bn),
    zero-padding the short last block of each expert."""
    E, K, F = w.shape
    pad = n_blocks * bn - F
    w_p = jnp.pad(w, ((0, 0), (0, 0), (0, pad))) if pad else w
    return (
        w_p.reshape(E, K, n_blocks, bn)
        .transpose(0, 2, 1, 3)
        .reshape(E * n_blocks, K, bn)
    )


def fold_lm_expert_weight(
    w: jax.Array, meta: dict, pair_block_n: int = 0
) -> jax.Array:
    """Dense (E, K, F) equivalent of the per-expert paired weights.

    The expert-axis analogue of :func:`fold_lm_weight` (backward-pass
    function and test oracle); same scatter-add zero-lane trick."""
    E, K, F = w.shape
    if meta["I"].ndim == 3:  # blocked-within-expert: (E, Bc, Pmax) lanes
        bn = pair_block_n
        Bc = meta["I"].shape[1]
        assert bn >= 1 and Bc == -(-F // bn), (Bc, F, bn)
        m = {k: v.reshape(E * Bc, *v.shape[2:]) for k, v in meta.items()}
        kmat, w_res = _take_block_segments(_expert_blocked_weights(w, Bc, bn), m)
        bar = jnp.arange(E * Bc)[:, None]
        wf_t = (
            jnp.zeros((E * Bc, K, bn), w.dtype)
            .at[bar, m["I"]].add(kmat)
            .at[bar, m["J"]].add(-kmat)
            .at[bar, m["resid"]].add(w_res)
        )
        return (
            wf_t.reshape(E, Bc, K, bn)
            .transpose(0, 2, 1, 3)
            .reshape(E, K, Bc * bn)[:, :, :F]
        )
    kmat, w_res = _take_block_segments(w, meta)  # expert = one block of F cols
    bar = jnp.arange(E)[:, None]
    return (
        jnp.zeros_like(w)
        .at[bar, meta["I"]].add(kmat)
        .at[bar, meta["J"]].add(-kmat)
        .at[bar, meta["resid"]].add(w_res)
    )


@functools.lru_cache(maxsize=None)
def _fused_paired_expert_dense_grad(
    activation, blocked, pair_block_n, x_per_expert, block_m, block_k, interpret
):
    """custom_vjp factory for the expert-axis paired GEMM — the same
    Pallas-forward / folded-XLA-backward split as _fused_paired_dense_grad,
    with the expert axis riding the blocked kernel's grid dimension."""
    from repro.kernels.paired_matmul import ACTIVATIONS

    def primal(x, w, meta):
        E, K, F = w.shape
        if blocked:
            Bc = meta["I"].shape[1]
            bn = pair_block_n
            m = {k: v.reshape(E * Bc, *v.shape[2:]) for k, v in meta.items()}
            kmat, w_res = _take_block_segments(
                _expert_blocked_weights(w, Bc, bn), m
            )
        else:
            Bc, bn = 1, F
            m = meta
            kmat, w_res = _take_block_segments(w, meta)
        perm = jnp.concatenate([m["I"], m["J"], m["resid"]], axis=-1)
        if x_per_expert:
            gather = lambda xe, pe: jnp.moveaxis(jnp.take(xe, pe, axis=-1), -2, 0)
            xg = jax.vmap(gather)(x, perm.reshape(E, Bc, -1))  # (E, Bc, M, K')
            xg = xg.reshape(E * Bc, x.shape[-2], -1)
        else:
            xg = jnp.moveaxis(jnp.take(x, perm, axis=-1), -2, 0)  # (E·Bc, M, K')
        y = on_mesh(functools.partial(
            paired_matmul_blocked, n_cols=E * Bc * bn, activation=activation,
            block_m=block_m, block_k=block_k, interpret=interpret,
        ))(xg, kmat.astype(x.dtype), w_res.astype(x.dtype))
        return y.reshape(x.shape[-2], E, Bc * bn)[..., :F]

    def ref(x, w, meta):
        wf = fold_lm_expert_weight(w, meta, pair_block_n)
        eq = "etk,ekf->tef" if x_per_expert else "tk,ekf->tef"
        return ACTIVATIONS[activation](jnp.einsum(eq, x, wf))

    @jax.custom_vjp
    def f(x, w, meta):
        return primal(x, w, meta)

    def fwd(x, w, meta):
        return primal(x, w, meta), (x, w, meta)

    def bwd(saved, dy):
        x, w, meta = saved
        _, vjp = jax.vjp(lambda x, w: ref(x, w, meta), x, w)
        dx, dw = vjp(dy)
        dmeta = {
            k: np.zeros(jnp.shape(a), jax.dtypes.float0)
            if jnp.issubdtype(jnp.result_type(a), jnp.integer)
            else jnp.zeros_like(a)
            for k, a in meta.items()
        }
        return dx, dw.astype(w.dtype), dmeta

    f.defvjp(fwd, bwd)
    return f


def fused_paired_expert_dense(
    x: jax.Array,  # (M, K) shared or (E, M, K) per-expert activations
    w: jax.Array,  # (E, K, F) live expert weights (one layer's scan slice)
    meta: dict,  # (E, …) pairing metadata (core.transform.pair_params)
    *,
    activation: str = "none",
    x_per_expert: bool = False,
    pair_block_n: int = 0,
    block_m: int = 0,
    block_k: int = 0,
    interpret: bool | None = None,
) -> jax.Array:
    """Differentiable per-expert paired GEMM → (M, E, F).

    The MoE analogue of :func:`fused_paired_dense`: ``meta`` holds one frozen
    lane structure *per expert* — ``(E, Pmax)`` lane lists select the
    structured-per-expert layout (each expert = one kernel block of all F
    columns), ``(E, Bc, Pmax)`` the blocked-within-expert one
    (``pair_block_n`` columns per block, as the metadata was built).
    ``x_per_expert=True`` contracts expert ``e``'s rows ``x[e]`` against
    expert ``e``'s weights (the "etk,ekf->tef" einsum); otherwise every
    expert sees the same (M, K) activations ("tk,ekf->tef").
    """
    blocked = meta["I"].ndim == 3
    if blocked and pair_block_n < 1:
        raise ValueError("blocked expert pairing metadata needs pair_block_n >= 1")
    fn = _fused_paired_expert_dense_grad(
        activation, blocked, pair_block_n if blocked else 0,
        bool(x_per_expert), block_m, block_k, interpret,
    )
    return fn(x, w, dict(meta))


# ---------------------------------------------------------------------------
# differentiable fused decode attention feeding the paired out-projection
# ---------------------------------------------------------------------------
#
# The decode-attention kernel (kernels.decode_attention) performs the paired
# out-projection in its flush step, so the attended values never reach HBM.
# The wrapper below normalizes whatever out-projection metadata the layer has
# — blocked (B, Pmax) lane lists, structured 1-D lists, or no pairing at all
# — into the kernel's column-blocked segment form:
#
#   * structured metadata lifts to one block of bn = N columns (``[None]`` on
#     every leaf) — the same layout ``fold_lm_weight`` treats as B == 1;
#   * an unpaired weight synthesizes a pure-residual block (one zero pair
#     lane with mask 0, ``resid = arange(K)``) so ``(o[I]-o[J])·kmat`` is
#     exactly zero and ``o[resid]·w_res == o @ W`` — the zero-lane trick;
#   * empty pair/residual segments (e.g. r=0 pairs nothing) pad to one zero
#     lane for the same reason, keeping every kernel operand non-empty.


def _attn_outproj_segments(w2: jax.Array, meta: dict | None, pair_block_n: int):
    """Normalized (idx_i, idx_j, idx_r, kmat, w_res) blocked segments of the
    out-projection for the fused decode-attention kernel."""
    K, N = w2.shape
    if meta is None:
        idx_i = idx_j = jnp.zeros((1, 1), jnp.int32)
        idx_r = jnp.arange(K, dtype=jnp.int32)[None]
        return idx_i, idx_j, idx_r, jnp.zeros((1, 1, N), w2.dtype), w2[None]
    if meta["I"].ndim == 1:
        meta = {k: v[None] for k, v in meta.items()}
        bn = N
    else:
        bn = pair_block_n
        assert bn >= 1, "blocked pairing metadata needs pair_block_n >= 1"
    kmat, w_res = _lm_blocked_segments(w2, meta, bn)
    idx_i, idx_j, idx_r = meta["I"], meta["J"], meta["resid"]
    B = idx_i.shape[0]
    if idx_i.shape[1] == 0:
        idx_i = idx_j = jnp.zeros((B, 1), jnp.int32)
        kmat = jnp.zeros((B, 1, bn), kmat.dtype)
    if idx_r.shape[1] == 0:
        idx_r = jnp.zeros((B, 1), jnp.int32)
        w_res = jnp.zeros((B, 1, bn), w_res.dtype)
    return idx_i, idx_j, idx_r, kmat, w_res


@functools.lru_cache(maxsize=None)
def _fused_attn_decode_grad(
    pair_block_n, window, n_sink, k_chunk, interpret, has_meta, has_residual
):
    """custom_vjp factory for the fused decode-attention + out-projection op.

    Pallas forward, XLA-reference backward (the same split as the paired
    GEMMs): the backward differentiates ``decode_attention_ref`` composed
    with the *folded* dense out-projection equivalent — decode attention is
    inference-only today, so the VJP exists to keep the op safely
    differentiable (a grad probe, a perplexity eval) rather than to be a
    training-speed path.  ``pos`` and the integer metadata get float0
    cotangents."""
    from repro.kernels.decode_attention import (
        decode_attention_ref,
        fused_decode_attention,
    )

    def primal(q, k_cache, v_cache, pos, w2, res, meta):
        N = w2.shape[1]
        idx_i, idx_j, idx_r, kmat, w_res = _attn_outproj_segments(
            w2, meta if has_meta else None, pair_block_n
        )
        res2 = None if res is None else res.reshape(-1, N)
        y = fused_decode_attention(
            q, k_cache, v_cache, pos, idx_i, idx_j, idx_r,
            kmat.astype(q.dtype), w_res.astype(q.dtype), res2,
            n_cols=N, window=window, n_sink=n_sink, k_chunk=k_chunk,
            interpret=interpret,
        )
        return y[:, None]  # (B, 1, N)

    def ref(q, k_cache, v_cache, pos, w2, res, meta):
        out = decode_attention_ref(
            q, k_cache, v_cache, pos, window=window, n_sink=n_sink
        )
        wf = fold_lm_weight(w2, meta, pair_block_n) if has_meta else w2
        o2 = out.reshape(*out.shape[:2], -1)
        z = jnp.einsum("bsk,kn->bsn", o2, wf.astype(o2.dtype))
        return z + res.astype(z.dtype) if res is not None else z

    @jax.custom_vjp
    def f(q, k_cache, v_cache, pos, w2, res, meta):
        return primal(q, k_cache, v_cache, pos, w2, res, meta)

    def fwd(q, k_cache, v_cache, pos, w2, res, meta):
        return primal(q, k_cache, v_cache, pos, w2, res, meta), (
            q, k_cache, v_cache, pos, w2, res, meta
        )

    def bwd(saved, dy):
        q, k_cache, v_cache, pos, w2, res, meta = saved
        _, vjp = jax.vjp(
            lambda q, kc, vc, w2, res: ref(q, kc, vc, pos, w2, res, meta),
            q, k_cache, v_cache, w2, res,
        )
        dq, dk, dv, dw, dres = vjp(dy)
        dpos = np.zeros(jnp.shape(pos), jax.dtypes.float0)
        dmeta = {
            k: np.zeros(jnp.shape(a), jax.dtypes.float0)
            if jnp.issubdtype(jnp.result_type(a), jnp.integer)
            else jnp.zeros_like(a)
            for k, a in meta.items()
        }
        return dq, dk, dv, dpos, dw.astype(w2.dtype), dres, dmeta

    f.defvjp(fwd, bwd)
    return f


def fused_attn_decode(
    q: jax.Array,  # (B, 1, H, D) one post-rope query row per slot
    k_cache: jax.Array,  # (B, S, KH, D)
    v_cache: jax.Array,
    pos: jax.Array,  # (B,) int32
    w: jax.Array,  # (K=H·D, N) live out-projection weights
    meta: dict | None = None,  # out-proj pairing metadata (any layout)
    *,
    residual: jax.Array | None = None,  # (B, 1, N) fused skip connection
    pair_block_n: int = 0,
    window: int = 0,
    n_sink: int = 0,
    k_chunk: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Differentiable fused decode attention + paired out-projection.

    One Pallas launch per decode step: online-softmax attention over the KV
    cache with the out-projection (and the sublayer residual) applied in the
    kernel flush — the attended values never round-trip HBM.  ``meta`` is
    the out-projection's frozen pairing structure in any of the LM layouts
    (1-D structured, 2-D blocked with ``pair_block_n``) or ``None`` for an
    unpaired weight (exact dense projection via a synthesized pure-residual
    block).  Returns (B, 1, N).
    """
    has_meta = meta is not None
    blocked = has_meta and meta["I"].ndim == 2
    if blocked and pair_block_n < 1:
        raise ValueError("blocked pairing metadata needs pair_block_n >= 1")
    fn = _fused_attn_decode_grad(
        pair_block_n if blocked else 0, window, n_sink, k_chunk, interpret,
        has_meta, residual is not None,
    )
    return fn(q, k_cache, v_cache, pos, w, residual,
              dict(meta) if has_meta else {})


# ---------------------------------------------------------------------------
# GEMM policy: route model-layer matmuls through the fused kernels
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class GemmPolicy:
    """Tile sizes + backend choice for layer GEMMs (0 → tuning heuristic)."""

    block_m: int = 0
    block_n: int = 0
    block_k: int = 0
    interpret: bool | None = None


_policy_state = threading.local()


def current_gemm_policy() -> GemmPolicy | None:
    return getattr(_policy_state, "policy", None)


@contextlib.contextmanager
def pallas_gemm(
    block_m: int = 0,
    block_n: int = 0,
    block_k: int = 0,
    interpret: bool | None = None,
):
    """Route :func:`repro.models.layers.dense` through the Pallas kernels.

    Thread-local, like :func:`repro.parallel.sharding.activate`; wrap the
    ``jax.jit`` trace (or the eager call) of a step to take effect.
    """
    prev = current_gemm_policy()
    _policy_state.policy = GemmPolicy(block_m, block_n, block_k, interpret)
    try:
        yield
    finally:
        _policy_state.policy = prev


@dataclasses.dataclass(frozen=True)
class PairedGemmPolicy:
    """Routing for the *paired* LM GEMM path (``gemm="pallas_paired"``).

    When active, :func:`repro.models.layers.dense` routes every GEMM whose
    weight carries pairing metadata (``core.transform.pair_lm_params``)
    through :func:`fused_paired_dense` — the subtractor kernel with the
    residual-add epilogue.  ``pair_block_n`` is the pairing block size the
    metadata was built with (0 → structured; it must match, the blocked
    kernel needs it to reassemble the packed column layout).
    """

    pair_block_n: int = 0
    block_m: int = 0
    block_n: int = 0
    block_k: int = 0
    interpret: bool | None = None


def current_paired_gemm_policy() -> PairedGemmPolicy | None:
    return getattr(_policy_state, "paired_gemm", None)


@contextlib.contextmanager
def pallas_paired_gemm(
    pair_block_n: int = 0,
    block_m: int = 0,
    block_n: int = 0,
    block_k: int = 0,
    interpret: bool | None = None,
):
    """Route pairing-annotated layer GEMMs through the subtractor kernel.

    Thread-local and trace-time, like :func:`pallas_gemm`; weights without
    pairing metadata keep their XLA einsum path.
    """
    prev = current_paired_gemm_policy()
    _policy_state.paired_gemm = PairedGemmPolicy(
        pair_block_n, block_m, block_n, block_k, interpret
    )
    try:
        yield
    finally:
        _policy_state.paired_gemm = prev


def gemm_context(knobs):
    """Context manager for a PerfKnobs-like object (``gemm``/``block_*``).

    ``knobs.gemm == "pallas"`` activates :func:`pallas_gemm` with the knob
    tile sizes; ``"pallas_paired"`` activates :func:`pallas_paired_gemm`
    (the subtractor path for pairing-annotated LM weights, honouring
    ``knobs.pair_block_n``); anything else is a no-op (XLA einsum path).
    """
    gemm = getattr(knobs, "gemm", "xla")
    if gemm == "pallas":
        return pallas_gemm(
            block_m=getattr(knobs, "block_m", 0),
            block_n=getattr(knobs, "block_n", 0),
            block_k=getattr(knobs, "block_k", 0),
        )
    if gemm == "pallas_paired":
        return pallas_paired_gemm(
            pair_block_n=getattr(knobs, "pair_block_n", 0),
            block_m=getattr(knobs, "block_m", 0),
            block_n=getattr(knobs, "block_n", 0),
            block_k=getattr(knobs, "block_k", 0),
        )
    return contextlib.nullcontext()


# ---------------------------------------------------------------------------
# conv policy: route model convolutions through im2col / the paired kernel
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ConvPolicy:
    """Conv lowering choice + artifacts for :func:`repro.models.lenet.lenet_apply`.

    ``impl`` is one of ``"xla"`` (lax.conv), ``"im2col"`` (patch GEMM via
    XLA) or ``"pallas_paired"`` (patch GEMM through the paired kernel, which
    additionally needs the per-layer ``paired`` artifacts from
    :func:`repro.core.transform.build_conv_pairings`).  ``fuse_pool`` makes
    the ``"pallas_paired"`` path absorb a following 2×2 max-pool into the
    kernel epilogue (the conv→pool megakernel: one HBM writeback, no
    standalone pooling op).  ``pair_block_n`` records the pairing mode the
    artifacts should be built with (0 → structured shared-row pairing;
    ``n >= 1`` → column-blocked pairing with that block size, ``1`` being
    the paper's per-column pairing) — :func:`conv_pairings_from_knobs`
    builds artifacts honouring it, via :func:`paired_mode_of`.
    """

    impl: str = "xla"
    paired: object = None  # {layer_name: PairedLayer} for "pallas_paired"
    fuse_pool: bool = False
    pair_block_n: int = 0
    block_m: int = 0
    block_n: int = 0
    block_k: int = 0
    interpret: bool | None = None


def current_conv_policy() -> ConvPolicy | None:
    return getattr(_policy_state, "conv", None)


@contextlib.contextmanager
def pallas_conv(
    impl: str = "pallas_paired",
    paired=None,
    fuse_pool: bool = False,
    pair_block_n: int = 0,
    block_m: int = 0,
    block_n: int = 0,
    block_k: int = 0,
    interpret: bool | None = None,
):
    """Thread-local conv policy, symmetric with :func:`pallas_gemm`.

    Model forwards that take ``conv_impl=None`` (lenet_apply) consult it at
    trace time; wrap the jit trace, not the jit call.
    """
    prev = current_conv_policy()
    _policy_state.conv = ConvPolicy(
        impl, paired, fuse_pool, pair_block_n,
        block_m, block_n, block_k, interpret
    )
    try:
        yield
    finally:
        _policy_state.conv = prev


def paired_mode_of(knobs_or_policy) -> tuple[str, int]:
    """(pairing mode, block_n) a ``pair_block_n`` knob encodes.

    ``0`` → ``("structured", 0)`` — today's shared-row pairing.  ``n >= 1``
    → ``("column_blocked", n)``: per-block shared-row pairing, ``n == 1``
    being the paper's per-column pairing.  Feed the result straight into
    ``build_conv_pairings(mode=…, block_n=…)`` / ``pair_model_params``.
    """
    n = int(getattr(knobs_or_policy, "pair_block_n", 0) or 0)
    return ("column_blocked", n) if n >= 1 else ("structured", 0)


def conv_pairings_from_knobs(params, rounding: float, knobs, *, positions=None):
    """Per-layer conv pairing artifacts honouring ``knobs.pair_block_n``.

    The offline half of the ``pair_block_n`` knob: build the
    ``build_conv_pairings`` artifacts in the mode the knob encodes
    (structured at 0, column-blocked at ``n >= 1``), ready to hand to
    ``conv_context(knobs, paired=…)`` / ``pallas_conv(paired=…)``.  Runs on
    concrete weights (numpy), like all pairing preprocessing.
    """
    from repro.core.transform import build_conv_pairings

    mode, block_n = paired_mode_of(knobs)
    return build_conv_pairings(
        params, rounding, positions=positions, mode=mode, block_n=block_n
    )


def conv_context(knobs, paired=None):
    """ConvPolicy context from a PerfKnobs-like object (``conv``/``block_*``).

    ``knobs.conv`` other than ``"xla"`` activates :func:`pallas_conv` with
    that implementation; ``paired`` supplies the per-layer artifacts the
    ``"pallas_paired"`` choice consumes, ``knobs.fuse_pool`` turns on the
    conv→pool megakernel epilogue, and ``knobs.pair_block_n`` records the
    pairing mode the artifacts were (or should be) built with.
    """
    impl = getattr(knobs, "conv", "xla")
    if impl != "xla":
        return pallas_conv(
            impl,
            paired=paired,
            fuse_pool=getattr(knobs, "fuse_pool", False),
            pair_block_n=getattr(knobs, "pair_block_n", 0),
            block_m=getattr(knobs, "block_m", 0),
            block_n=getattr(knobs, "block_n", 0),
            block_k=getattr(knobs, "block_k", 0),
        )
    return contextlib.nullcontext()


@dataclasses.dataclass(frozen=True)
class AttnPolicy:
    """Routing for decode attention (``attn="pallas_fused"``).

    When active, :func:`repro.models.layers.attention_decode_block` routes
    the single-token attention + out-projection through
    :func:`fused_attn_decode` — one Pallas launch whose flush applies the
    out-projection's subtractor segments and the sublayer residual in VMEM,
    so the attended values never round-trip HBM.  ``k_chunk`` is the KV-cache
    chunk the online softmax streams over.  Prefill paths are unaffected.
    """

    impl: str = "pallas_fused"
    k_chunk: int = 128
    interpret: bool | None = None


def current_attn_policy() -> AttnPolicy | None:
    return getattr(_policy_state, "attn", None)


@contextlib.contextmanager
def pallas_attn(
    impl: str = "pallas_fused",
    k_chunk: int = 128,
    interpret: bool | None = None,
):
    """Route single-token decode attention through the fused Pallas kernel.

    Thread-local and trace-time, like :func:`pallas_gemm`; wrap the jit
    trace of the decode step, not the jit call.
    """
    prev = current_attn_policy()
    _policy_state.attn = AttnPolicy(impl, k_chunk, interpret)
    try:
        yield
    finally:
        _policy_state.attn = prev


def attn_context(knobs):
    """AttnPolicy context from a PerfKnobs-like object (``attn``/``k_chunk``).

    ``knobs.attn == "pallas_fused"`` activates :func:`pallas_attn` with the
    knob's KV chunk; anything else is a no-op (the XLA decode-attention
    einsums + the standalone out-projection GEMM).
    """
    impl = getattr(knobs, "attn", "xla")
    if impl == "pallas_fused":
        return pallas_attn(impl, k_chunk=getattr(knobs, "k_chunk", 128) or 128)
    return contextlib.nullcontext()


def tile_cache_context(knobs):
    """``knobs.tile_cache`` (a path) installs a persisted TileCache so the
    kernels' tile selection prefers measured winners over the heuristic;
    empty/absent is a no-op (heuristic only).  Trace-time, like the other
    policies: choose_blocks runs while the step is being traced."""
    path = getattr(knobs, "tile_cache", "")
    if path:
        return tuning.use_tile_cache(path)
    return contextlib.nullcontext()


@contextlib.contextmanager
def perf_context(knobs, paired=None):
    """Activate every kernel policy a PerfKnobs asks for (gemm + conv +
    attn + tile cache)."""
    with tile_cache_context(knobs), gemm_context(knobs), conv_context(
        knobs, paired=paired
    ), attn_context(knobs):
        yield
