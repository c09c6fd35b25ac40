"""Pallas TPU kernel: the paper's subtractor MAC array, as a fused GEMM.

The ASIC datapath of the paper evaluates a combined weight pair (+k, -k) as
``k · (I₁ − I₂)`` — one subtractor replaces a multiplier+adder (eq. 1).  On a
TPU the MXU charges the same for every multiply-accumulate lane, so the
*structural* translation of the saving is a **shorter contraction**: with
``P`` shared pairs and ``R`` residual channels (``K = 2P + R``),

    y = (x[:, :P] − x[:, P:2P]) @ Kmat  +  x[:, 2P:] @ W_res

contracts over ``P + R = K − P`` lanes instead of ``K``.  The subtraction is
VPU work fused into the same kernel — it never round-trips HBM.  The input
is expected *pre-permuted* to the ``[I | J | residual]`` layout
(``StructuredPairing.perm()``); the permutation is free at deploy time
because it folds into the previous layer's output projection.

Kernel tiling
=============
The kernel runs on a three-dimensional grid ``(M/bm, N/bn, nk)`` with the
contraction dimension innermost, so all k-steps of one output tile execute
back-to-back on the same core:

* each program loads a ``(bm, bk)`` activation tile and a ``(bk, bn)``
  weight tile into VMEM — never a full-K row block.  That is what lets the
  same kernel serve LeNet (K = 400) and the production configs the ROADMAP
  names (mistral-large ``d_model`` 12288, d_ff 28672) without blowing the
  ~16 MB VMEM budget;
* partial products accumulate into a ``(bm, bn)`` **fp32 VMEM scratch**
  accumulator, zero-initialised at ``k == 0`` and flushed to the output ref
  at the last k-step (``jax.experimental.pallas`` revisits the same output
  block for every k, so the flush races nothing);
* the contraction axis is *segmented*: the first ``nkp`` k-steps walk the
  paired lanes (subtract-then-MAC over ``Kmat``), the remaining ``nkr``
  steps walk the residual lanes (plain MAC over ``W_res``).  Segment
  boundaries are static, so ``pl.when`` predication costs one scalar compare
  per step; block index maps clamp into their own segment.  ``P == 0`` or
  ``R == 0`` simply drop a segment — the three historical ``pallas_call``
  branches are now one parameterized builder (``_build_paired_call``);
* the **epilogue is fused**: bias add and an optional activation
  (relu / gelu / silu / tanh) happen on the fp32 accumulator right before
  the flush, so downstream layers stop paying an extra HBM round-trip for
  ``y + b`` / ``act(y)``.

Per-segment k-tiles are padded with zero lanes up to a ``bk`` multiple;
zero activation lanes × zero weight rows contribute nothing, so no masking
is needed in the accumulation.

Residual-add epilogue
=====================
``residual=`` streams an ``(M, N)`` operand (output-space, e.g. the skip
branch of a Transformer sublayer) into the flush: it is added on the fp32
accumulator *after* bias + activation (and after the pooled window
reduction, when pooling is fused), immediately before the single HBM
writeback.  A decoder's ``h + attn_out(x)`` / ``h + mlp(x)`` therefore
stops being a standalone XLA add over a full hidden-state tensor — the
skip connection rides the same kernel writeback.  The residual may arrive
in a different dtype than the activations (bf16 skip against an fp32
accumulator is the common serving case); it is promoted to fp32 for the
add and the result is cast once to the output dtype.

Fused pooling epilogue (the conv→pool→activation megakernel)
============================================================
With ``pool="max2"`` / ``"avg2"`` the kernel additionally reduces a 2×2
spatial window *inside VMEM* before its single HBM writeback — the serving
path for conv→pool stops round-tripping the full activation map through
HBM.  The caller pre-arranges the GEMM rows **window-major**: the activation
operand is ``(4, M, K)`` where axis 0 enumerates the 2×2 window elements of
pooled output row ``m`` (see ``paired_conv``'s layout transform).  Each
program then accumulates a ``(4, bm, bn)`` fp32 scratch (four 2-D MXU dots
per k-step — the window axis is a leading, untiled dimension, which Mosaic
handles without sublane reshapes), applies bias → activation on the full
window, reduces over the window axis, and flushes only the ``(bm, bn)``
*pooled* tile.  The HBM writeback shrinks 4×, and the separate pooling op
disappears from the schedule.

Column-blocked layout (per-n-block pairings)
============================================
The paper's per-column pairing gives every output channel its own lane
permutation; the structured layout above shares one across all N.  The
*column-blocked* mode interpolates: ``core.pairing.pair_rows_blocked``
computes an independent shared-row pairing per group of ``block_n`` output
channels, and :func:`paired_matmul_blocked_pallas` executes it by giving
**each grid n-step its own segment metadata**.  Operands arrive block-major:

* activations are pre-gathered through the packed ``(n_blocks, K')`` index
  matrix (``BlockedPairing.index_arrays()["perm"]``, one XLA gather) into
  ``(n_blocks, M, K')`` — block ``b``'s rows permuted to *its* ``[I | J |
  resid]`` order, every block padded to the common ``(Pmax, Rmax)`` split
  (``K' = 2·Pmax + Rmax``);
* weights are packed ``(n_blocks, Pmax, bn)`` / ``(n_blocks, Rmax, bn)``
  with zero rows on the padding, so padded lanes contract against zeros and
  need no masking — exactly the zero-lane trick the k-tile padding already
  uses;
* the grid becomes ``(M/bm, n_blocks, nk)`` and every operand spec carries a
  leading block axis indexed by the n-step, so the k-segmentation, fp32
  accumulator, fused epilogue and pooling epilogue all run unchanged *per
  block* — the kernel body only swaps its tile accessors.

The gather must happen outside the kernel: a k-tiled stream can only DMA
contiguous lane blocks, and a block's paired lanes are scattered across the
full K — pre-gathering (which XLA fuses with the im2col patch extraction)
is what keeps the contraction K-tiled.  The cost is the activation
replication factor ``n_blocks`` (the paper's per-column dataflow at
``block_n = 1`` fundamentally reads each input once per output channel's
subtract schedule); ``block_n`` is the knob trading that bandwidth against
pairing rate.

``interpret=None`` (every entry's default) resolves through
:func:`resolve_interpret`: the compiled Mosaic kernel on a TPU backend, the
Pallas interpreter executing the same kernel body anywhere else (that is how
the CPU test suite validates it).
"""
from __future__ import annotations

from collections.abc import Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Epilogue activations the kernel can fuse. "none" is the identity.
ACTIVATIONS: dict[str, Callable] = {
    "none": lambda x: x,
    "relu": jax.nn.relu,
    "gelu": jax.nn.gelu,
    "silu": jax.nn.silu,
    "swish": jax.nn.silu,
    "tanh": jnp.tanh,
}

# Fused 2×2 window reductions over the leading (window) axis of the fp32
# accumulator. "none" means no pooling (2-D kernel layout).
POOLS: dict[str, Callable] = {
    "max2": lambda a: a.max(axis=0),
    "avg2": lambda a: a.mean(axis=0),
}
POOL_WINDOW = 4  # 2×2 — the only window geometry LeNet (and the paper) uses


def resolve_interpret(interpret: bool | None = None) -> bool:
    """The one place ``interpret=None`` is resolved for every kernel entry
    and policy: compile the Mosaic kernel on a TPU backend, run the Pallas
    interpreter anywhere else.  An explicit bool is honoured as given."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def mxu_dot(a: jax.Array, b: jax.Array, contract_b: int = 0) -> jax.Array:
    """2-D MXU contraction of ``a (m, k)`` with ``b`` — ``(k, n)`` at
    ``contract_b=0``, ``(n, k)`` at ``contract_b=1`` — accumulating in fp32.
    fp32 operands contract at full fp32 precision (the TPU's default would
    round them to bf16 for a single MXU pass); bf16 products are exact
    either way."""
    assert a.dtype == b.dtype, (a.dtype, b.dtype)
    prec = jax.lax.Precision.HIGHEST if a.dtype == jnp.float32 else None
    return jax.lax.dot_general(
        a, b, (((1,), (contract_b,)), ((), ())),
        precision=prec, preferred_element_type=jnp.float32,
    )


def _apply_epilogue(acc, bias_block, activation: str):
    """Bias add + activation on the fp32 accumulator (pre-flush)."""
    if bias_block is not None:
        acc = acc + bias_block.astype(jnp.float32)
    return ACTIVATIONS[activation](acc)


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


def _pad_to(arr: jax.Array, axis: int, size: int) -> jax.Array:
    if arr.shape[axis] == size:
        return arr
    pads = [(0, 0)] * arr.ndim
    pads[axis] = (0, size - arr.shape[axis])
    return jnp.pad(arr, pads)


def _build_paired_call(
    *,
    bm: int,
    bn: int,
    nkp: int,
    bkp: int,
    nkr: int,
    bkr: int,
    has_bias: bool,
    has_residual: bool,
    activation: str,
    pool: str,
    Mp: int,
    Np: int,
    out_dtype,
    interpret: bool,
    n_blocks: int = 0,
):
    """One parameterized ``pallas_call`` covering all segment combinations.

    The contraction grid has ``nkp`` paired k-steps followed by ``nkr``
    residual k-steps; either count may be zero (but not both).  Inputs are
    ordered ``[xi, xj, kmat][:has_pairs] + [xr, w_res][:has_resid] +
    [bias][:has_bias] + [residual][:has_residual]``.

    ``has_residual`` streams an output-shaped ``(Mp, Np)`` operand added on
    the fp32 accumulator in the flush, after bias/activation (and after the
    pooled reduction) — the fused skip connection.  It is indexed like the
    output tile, so it works identically in the blocked layout (the
    residual lives in output space; blocks only partition the contraction
    metadata).

    ``pool != "none"`` selects the megakernel layout: activation operands
    are window-major ``(4, Mp, K)``, the accumulator grows a leading window
    axis, and the flush reduces the 2×2 window before the (single, pooled)
    HBM writeback.  ``Mp`` then counts *pooled* output rows.

    ``n_blocks > 0`` selects the column-blocked layout (module docstring,
    "Column-blocked layout"): every operand carries a leading block axis
    indexed by the grid n-step (block shape 1), so each n-step contracts
    against its own ``[I | J | resid]`` segment metadata; the grid n extent
    is ``n_blocks`` and ``Np == n_blocks · bn``.  The output, bias and
    residual are block-major too — ``(n_blocks, Mp, bn)`` and ``(n_blocks,
    1, bn)`` — so each block's trailing dims are whole array dims and the
    layout is tile-legal on the TPU for any ``bn``, down to the paper's
    per-column ``bn = 1``.
    """
    has_pairs = nkp > 0
    has_resid = nkr > 0
    has_pool = pool != "none"
    blocked = n_blocks > 0
    W = POOL_WINDOW if has_pool else 1
    nk = nkp + nkr
    assert nk > 0
    if blocked:
        assert Np == n_blocks * bn, (Np, n_blocks, bn)

    # The TPU MXU multiplies bf16 operands at full product precision and
    # accumulates fp32; XLA's *CPU* dot instead rounds each product to bf16.
    # Interpret mode is the validation oracle, so upcast dot operands there
    # to match the hardware semantics being modelled.
    cast = (lambda a: a.astype(jnp.float32)) if interpret else (lambda a: a)

    def sub(a, b):
        # The paper's subtractor operates at *input* precision: for bf16
        # inputs the difference is rounded to bf16 before it feeds the MXU.
        # reduce_precision pins that rounding — XLA's excess-precision pass
        # would otherwise elide the bf16 round-trip inside the fused kernel
        # and silently diverge from the hardware dataflow (and from ref.py).
        d = a - b
        if interpret and d.dtype != jnp.float32:
            info = jnp.finfo(d.dtype)
            d = jax.lax.reduce_precision(
                d.astype(jnp.float32), info.nexp, info.nmant
            )
        return d

    def kernel(*refs):
        refs = list(refs)
        acc_ref = refs.pop()
        o_ref = refs.pop()
        r_ref = refs.pop() if has_residual else None
        b_ref = refs.pop() if has_bias else None
        it = iter(refs)
        k = pl.program_id(2)

        # Window-element accessors: with pooling the activation refs carry a
        # leading window axis and the accumulator matches; each window
        # element runs its own 2-D MXU dot (the window axis stays a leading,
        # untiled dim — no sublane reshapes).  In the blocked layout every
        # operand additionally carries a leading (size-1) block axis — the
        # n-step already selected the block, so the accessors just squeeze.
        def x_at(ref, w):
            if blocked:
                return ref[0, w] if has_pool else ref[0]
            return ref[w] if has_pool else ref[...]

        def w_tile(ref):
            return ref[0] if blocked else ref[...]

        out_tile = w_tile  # output-space blocks (bias, residual) likewise

        def acc_add(w, val):
            if has_pool:
                acc_ref[w] = acc_ref[w] + val
            else:
                acc_ref[...] += val

        @pl.when(k == 0)
        def _zero():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        if has_pairs:
            xi_ref, xj_ref, km_ref = next(it), next(it), next(it)

            def paired_step():
                # VPU subtract (the paper's subtractor) at input precision,
                # then one MXU dot per window element.
                km = cast(w_tile(km_ref))
                for w in range(W):
                    diff = sub(x_at(xi_ref, w), x_at(xj_ref, w))
                    acc_add(w, mxu_dot(cast(diff), km))

            if has_resid:
                pl.when(k < nkp)(paired_step)
            else:
                paired_step()
        if has_resid:
            xr_ref, wr_ref = next(it), next(it)

            def resid_step():
                wr = cast(w_tile(wr_ref))
                for w in range(W):
                    acc_add(w, mxu_dot(cast(x_at(xr_ref, w)), wr))

            if has_pairs:
                pl.when(k >= nkp)(resid_step)
            else:
                resid_step()

        @pl.when(k == nk - 1)
        def _flush():
            bias_block = out_tile(b_ref) if has_bias else None
            acc = _apply_epilogue(acc_ref[...], bias_block, activation)
            if has_pool:
                acc = POOLS[pool](acc)  # (4, bm, bn) → (bm, bn) in VMEM
            if has_residual:
                # fused skip connection: fp32 add after bias/activation/pool,
                # still inside VMEM — the residual never costs its own HBM
                # round-trip through a standalone add op
                acc = acc + out_tile(r_ref).astype(jnp.float32)
            if blocked:
                o_ref[0] = acc.astype(o_ref.dtype)
            else:
                o_ref[...] = acc.astype(o_ref.dtype)

    # --- block specs: each segment's index map clamps into its own range ---
    # (with pooling, activation blocks carry the full window axis up front;
    # in the blocked layout every operand leads with a block axis the grid
    # n-step indexes)
    def x_spec(bk, kidx):
        if blocked:
            if has_pool:
                return pl.BlockSpec(
                    (1, W, bm, bk), lambda m, n, k: (n, 0, m, kidx(k))
                )
            return pl.BlockSpec((1, bm, bk), lambda m, n, k: (n, m, kidx(k)))
        if has_pool:
            return pl.BlockSpec((W, bm, bk), lambda m, n, k: (0, m, kidx(k)))
        return pl.BlockSpec((bm, bk), lambda m, n, k: (m, kidx(k)))

    def w_spec(bk, kidx):
        if blocked:
            return pl.BlockSpec((1, bk, bn), lambda m, n, k: (n, kidx(k), 0))
        return pl.BlockSpec((bk, bn), lambda m, n, k: (kidx(k), n))

    in_specs = []
    if has_pairs:
        pk = lambda k: jnp.minimum(k, nkp - 1)
        in_specs += [x_spec(bkp, pk), x_spec(bkp, pk), w_spec(bkp, pk)]
    if has_resid:
        rk = lambda k: jnp.clip(k - nkp, 0, nkr - 1)
        in_specs += [x_spec(bkr, rk), w_spec(bkr, rk)]
    if blocked:
        out_spec = pl.BlockSpec((1, bm, bn), lambda m, n, k: (n, m, 0))
        bias_spec = pl.BlockSpec((1, 1, bn), lambda m, n, k: (n, 0, 0))
        out_shape = (n_blocks, Mp, bn)
    else:
        out_spec = pl.BlockSpec((bm, bn), lambda m, n, k: (m, n))
        bias_spec = pl.BlockSpec((1, bn), lambda m, n, k: (0, n))
        out_shape = (Mp, Np)
    if has_bias:
        in_specs.append(bias_spec)
    if has_residual:
        # output-space operand: indexed exactly like the output tile
        in_specs.append(out_spec)

    # name the kernel by its segments: profiles (and the dtype analysis rule,
    # which pins reduce_precision on low-precision *subtractor* kernels) key
    # on "paired" meaning the kernel actually executes x[I]-x[J] lanes
    name = "paired_matmul" if has_pairs else "dense_matmul"
    if blocked:
        name += "_blocked"
    if has_pool:
        name += "_pool"

    acc_shape = (W, bm, bn) if has_pool else (bm, bn)
    return pl.pallas_call(
        kernel,
        name=name,
        grid=(Mp // bm, n_blocks if blocked else Np // bn, nk),
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct(out_shape, out_dtype),
        scratch_shapes=[pltpu.VMEM(acc_shape, jnp.float32)],
        # k must iterate sequentially per output tile (the accumulator
        # carries across k-steps); m/n tiles are independent
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )


def paired_matmul_pallas(
    x: jax.Array,  # (M, K) pre-permuted to [I | J | residual]
    kmat: jax.Array,  # (P, N) per-column pair magnitudes
    w_res: jax.Array,  # (R, N) residual weights, R = K - 2P
    bias: jax.Array | None = None,  # (N,) fused epilogue bias
    *,
    residual: jax.Array | None = None,  # (M, N) fused skip-connection add
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
    activation: str = "none",
    pool: str = "none",
    interpret: bool | None = None,
) -> jax.Array:
    """K-tiled fused subtract-then-MAC GEMM with epilogue. Returns (M, N).

    The contraction over ``P`` paired lanes and ``R`` residual lanes is
    tiled in ``block_k`` chunks with an fp32 VMEM accumulator (see the
    module docstring, "Kernel tiling").

    ``pool="max2"``/``"avg2"`` selects the megakernel: ``x`` must then be
    window-major ``(4, M, K)`` — axis 0 enumerating the 2×2 window elements
    of pooled output row ``m`` — and the result is the *pooled* ``(M, N)``
    map, reduced in VMEM before the single HBM writeback (see the module
    docstring, "Fused pooling epilogue").

    ``residual`` fuses an output-shaped skip-connection add into the flush
    (after bias/activation/pool, fp32, before the single writeback — see
    the module docstring, "Residual-add epilogue"); with pooling it must
    already be the pooled ``(M, N)`` map.
    """
    assert pool == "none" or pool in POOLS, f"unknown pool {pool!r}"
    has_pool = pool != "none"
    if has_pool:
        assert x.ndim == 3 and x.shape[0] == POOL_WINDOW, (
            f"pool={pool!r} expects window-major x (4, M, K), got {x.shape}"
        )
    else:
        assert x.ndim == 2, f"expected (M, K) activations, got {x.shape}"
    M, K = x.shape[-2], x.shape[-1]
    P, N = kmat.shape
    R = w_res.shape[0]
    assert K == 2 * P + R, f"layout mismatch: K={K} vs 2P+R={2*P+R}"
    assert activation in ACTIVATIONS, f"unknown activation {activation!r}"
    if residual is not None:
        assert residual.shape == (M, N), (
            f"residual must be output-shaped {(M, N)}, got {residual.shape}"
        )

    xi = x[..., :P]
    xj = x[..., P : 2 * P]
    xr = x[..., 2 * P :]

    if P + R == 0:
        # degenerate zero-length contraction: epilogue only
        y = jnp.zeros(((POOL_WINDOW, M, N) if has_pool else (M, N)), jnp.float32)
        b = None if bias is None else bias.astype(jnp.float32)[None]
        y = _apply_epilogue(y, b, activation)
        if has_pool:
            y = POOLS[pool](y)
        if residual is not None:
            y = y + residual.astype(jnp.float32)
        return y.astype(x.dtype)

    m_axis, k_axis = x.ndim - 2, x.ndim - 1
    bm = min(block_m, M)
    bn = min(block_n, N)
    Mp = _ceil_to(M, bm)
    Np = _ceil_to(N, bn)

    # per-segment k tiles (each segment keeps its own block size ≤ block_k)
    bkp = min(block_k, P) if P else 0
    bkr = min(block_k, R) if R else 0
    nkp = -(-P // bkp) if P else 0
    nkr = -(-R // bkr) if R else 0

    operands = []
    if P:
        Pp = nkp * bkp
        operands += [
            _pad_to(_pad_to(xi, m_axis, Mp), k_axis, Pp),
            _pad_to(_pad_to(xj, m_axis, Mp), k_axis, Pp),
            _pad_to(_pad_to(kmat, 0, Pp), 1, Np),
        ]
    if R:
        Rp = nkr * bkr
        operands += [
            _pad_to(_pad_to(xr, m_axis, Mp), k_axis, Rp),
            _pad_to(_pad_to(w_res, 0, Rp), 1, Np),
        ]
    if bias is not None:
        operands.append(_pad_to(bias[None], 1, Np))
    if residual is not None:
        operands.append(_pad_to(_pad_to(residual, 0, Mp), 1, Np))

    call = _build_paired_call(
        bm=bm, bn=bn, nkp=nkp, bkp=bkp, nkr=nkr, bkr=bkr,
        has_bias=bias is not None, has_residual=residual is not None,
        activation=activation, pool=pool,
        Mp=Mp, Np=Np, out_dtype=x.dtype, interpret=resolve_interpret(interpret),
    )
    out = call(*operands)
    return out[:M, :N]


def paired_matmul_blocked_pallas(
    x: jax.Array,  # (B, M, K') block-gathered, or (B, 4, M, K') window-major
    kmat: jax.Array,  # (B, Pmax, bn) packed per-block pair magnitudes
    w_res: jax.Array,  # (B, Rmax, bn) packed per-block residual weights
    bias: jax.Array | None = None,  # (N,) fused epilogue bias
    *,
    n_cols: int,
    residual: jax.Array | None = None,  # (M, n_cols) fused skip-connection add
    block_m: int = 128,
    block_k: int = 512,
    activation: str = "none",
    pool: str = "none",
    interpret: bool | None = None,
) -> jax.Array:
    """Column-blocked K-tiled paired GEMM. Returns (M, n_cols).

    Each of the ``B`` blocks owns ``bn`` contiguous output columns and its
    own ``[I | J | resid]`` lane segments, padded to the common
    ``(Pmax, Rmax)`` split (``K' = 2·Pmax + Rmax``; padded lanes carry zero
    weights).  ``x`` is the activation matrix already gathered through the
    packed index matrix (``BlockedPairing.index_arrays()["perm"]``), so row
    block ``b`` of ``x`` is permuted to block ``b``'s lane order.  Only the
    last block may cover fewer than ``bn`` real columns (``n_cols`` trims
    the padding); the lane tile is pinned to ``bn`` — the pairing block size
    *is* the kernel's n-tile.  Epilogue (bias + activation), the fused
    2×2 pooling (``x`` then ``(B, 4, M, K')`` window-major) and the
    residual-add epilogue (``residual`` lives in *output* space, so it is
    indexed like the output tile — blocks only partition the contraction
    metadata) behave exactly as in :func:`paired_matmul_pallas`, per block.

    The block axis doubles as the MoE **expert grid**: per-expert pairings
    (``core.transform.pair_params`` on ``(L, E, K, F)`` weights) map each
    expert — or each ``(expert, column-block)`` cell — onto one ``B`` entry
    with its own permuted activation rows, so
    :func:`repro.kernels.ops.fused_paired_expert_dense` runs the whole
    expert batch as a single blocked launch.
    """
    assert pool == "none" or pool in POOLS, f"unknown pool {pool!r}"
    has_pool = pool != "none"
    if has_pool:
        assert x.ndim == 4 and x.shape[1] == POOL_WINDOW, (
            f"pool={pool!r} expects block-major window-major x (B, 4, M, K'), "
            f"got {x.shape}"
        )
    else:
        assert x.ndim == 3, f"expected (B, M, K') activations, got {x.shape}"
    B, P, bn = kmat.shape
    R = w_res.shape[1]
    assert w_res.shape[0] == B and w_res.shape[2] == bn, (kmat.shape, w_res.shape)
    M, Kp = x.shape[-2], x.shape[-1]
    assert x.shape[0] == B, (x.shape, B)
    assert Kp == 2 * P + R, f"packed layout mismatch: K'={Kp} vs 2P+R={2*P+R}"
    assert 0 < n_cols <= B * bn, (n_cols, B, bn)
    assert activation in ACTIVATIONS, f"unknown activation {activation!r}"
    if residual is not None:
        assert residual.shape == (M, n_cols), (
            f"residual must be output-shaped {(M, n_cols)}, got {residual.shape}"
        )

    xi = x[..., :P]
    xj = x[..., P : 2 * P]
    xr = x[..., 2 * P :]

    if P + R == 0:
        y = jnp.zeros(((POOL_WINDOW, M, n_cols) if has_pool else (M, n_cols)),
                      jnp.float32)
        b = None if bias is None else bias.astype(jnp.float32)[None]
        y = _apply_epilogue(y, b, activation)
        if has_pool:
            y = POOLS[pool](y)
        if residual is not None:
            y = y + residual.astype(jnp.float32)
        return y.astype(x.dtype)

    m_axis, k_axis = x.ndim - 2, x.ndim - 1
    bm = min(block_m, M)
    Mp = _ceil_to(M, bm)
    Np = B * bn

    bkp = min(block_k, P) if P else 0
    bkr = min(block_k, R) if R else 0
    nkp = -(-P // bkp) if P else 0
    nkr = -(-R // bkr) if R else 0

    operands = []
    if P:
        Pp = nkp * bkp
        operands += [
            _pad_to(_pad_to(xi, m_axis, Mp), k_axis, Pp),
            _pad_to(_pad_to(xj, m_axis, Mp), k_axis, Pp),
            _pad_to(kmat, 1, Pp),
        ]
    if R:
        Rp = nkr * bkr
        operands += [
            _pad_to(_pad_to(xr, m_axis, Mp), k_axis, Rp),
            _pad_to(w_res, 1, Rp),
        ]
    # output-space operands go block-major, like the kernel's output
    if bias is not None:
        operands.append(_pad_to(bias, 0, Np).reshape(B, 1, bn))
    if residual is not None:
        res = _pad_to(_pad_to(residual, 0, Mp), 1, Np)
        operands.append(res.reshape(Mp, B, bn).transpose(1, 0, 2))

    call = _build_paired_call(
        bm=bm, bn=bn, nkp=nkp, bkp=bkp, nkr=nkr, bkr=bkr,
        has_bias=bias is not None, has_residual=residual is not None,
        activation=activation, pool=pool,
        Mp=Mp, Np=Np, out_dtype=x.dtype, interpret=resolve_interpret(interpret),
        n_blocks=B,
    )
    out = call(*operands)  # (B, Mp, bn)
    return out.transpose(1, 0, 2).reshape(Mp, Np)[:M, :n_cols]


def dense_matmul_pallas(
    x: jax.Array,
    w: jax.Array,
    bias: jax.Array | None = None,
    *,
    residual: jax.Array | None = None,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
    activation: str = "none",
    interpret: bool | None = None,
) -> jax.Array:
    """Baseline K-tiled GEMM with identical tiling + epilogue fusion.

    The degenerate single-segment case of the paired builder (P == 0):
    like-for-like comparison baseline and the serving fast path for
    unpaired layers.
    """
    P0 = jnp.zeros((0, w.shape[1]), w.dtype)
    return paired_matmul_pallas(
        x, P0, w, bias, residual=residual,
        block_m=block_m, block_n=block_n, block_k=block_k,
        activation=activation, interpret=interpret,
    )
