"""Pallas TPU kernel: single-token decode attention fused into the paired
out-projection.

The decode step is the memory-bound half of serving: one query row per slot
attends over the KV cache, and before this kernel existed the attended
values round-tripped HBM between the attention einsums and the paired
out-projection subtractor kernel — the one non-paired gap left in the decode
schedule (ROADMAP "Close the attention gap").  This kernel closes it: the
online softmax runs in fp32 VMEM scratch, and the flush step applies the
out-projection's column-blocked subtractor arithmetic *inside the kernel* —
``y[b] = (o[I]-o[J])·kmat + o[resid]·w_res (+ residual)`` — so the attended
vector is never materialized in HBM.  The ``residual=`` epilogue matches the
paired GEMM kernel's: an fp32 add in VMEM, no standalone residual add op.

Geometry: grid ``(B, nk)`` with the KV-chunk axis innermost (sequential —
the (m, l, acc) scratch carries across chunks of one slot's cache).  Every
block is tile-legal for Mosaic: the cache streams as ``(1, k_chunk, KH·D)``
lane-dense chunks (a free reshape of ``(B, S, KH, D)``), each KV head is a
128-aligned lane slice of it, and the per-slot positions live in SMEM.  GQA
scores every query head against each KV head on the MXU and keeps the rows
of that head's group — two-dimensional dots only, no in-kernel reshapes.
Masking matches ``layers._block_mask`` decode semantics exactly: keys at
``pk <= pos``, restricted to the sliding window ``pk > pos - window`` when
one is set, with the first ``n_sink`` (meta-token) positions always visible.
Chunks fully outside the mask are skipped via ``pl.when`` without touching
the MXU; every chunk that runs holds at least one visible key, so the
running max is finite after it.

The out-projection gathers the attended lanes with one-hot contractions on
the MXU (Mosaic has no lane gather): per query head, ``o[h] @ (e_I − e_J)``
yields the head's share of ``o[I] − o[J]`` and ``o[h] @ e_resid`` its
residual lanes, exactly, since every one-hot column selects one lane.  The
subtractor difference is then rounded to the I/O dtype before it feeds the
MXU — the input-precision semantics of the paired GEMM kernel, so the fused
and unfused decode schedules run the same arithmetic.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.paired_matmul import _pad_to, mxu_dot, resolve_interpret


def decode_attention_ref(
    q: jax.Array,  # (B, 1, H, D)
    k_cache: jax.Array,  # (B, S, KH, D)
    v_cache: jax.Array,
    pos: jax.Array,  # (B,) int32
    *,
    window: int = 0,
    n_sink: int = 0,
) -> jax.Array:
    """XLA reference for the attention half (mirrors
    ``layers.decode_attention``) — the custom-VJP backward differentiates
    this instead of the kernel."""
    B, _, H, D = q.shape
    _, S, KH, _ = k_cache.shape
    G = H // KH
    qg = q[:, 0].reshape(B, KH, G, D).astype(jnp.float32)
    k = k_cache.astype(jnp.float32)
    v = v_cache.astype(jnp.float32)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k) * (1.0 / math.sqrt(D))
    pk = jnp.arange(S)[None, None, None, :]
    p_ = pos[:, None, None, None]
    ok = pk <= p_
    if window:
        in_w = pk > p_ - window
        if n_sink:
            in_w = in_w | (pk < n_sink)
        ok = ok & in_w
    s = jnp.where(ok, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p, v)
    return out.reshape(B, 1, H, D).astype(q.dtype)


def _decode_attn_kernel(
    *refs,
    scale: float,
    window: int,
    n_sink: int,
    k_chunk: int,
    nk: int,
    KH: int,
    G: int,
    D: int,
    proj: bool,
    has_residual: bool,
    io_dtype,
):
    it = iter(refs)
    pos_ref, q_ref, k_ref, v_ref = next(it), next(it), next(it), next(it)
    if proj:
        i_ref, j_ref, r_ref = next(it), next(it), next(it)
        km_ref, wr_ref = next(it), next(it)
    resid_ref = next(it) if has_residual else None
    o_ref, m_ref, l_ref, acc_ref = next(it), next(it), next(it), next(it)

    H = KH * G
    ki = pl.program_id(1)
    pos = pos_ref[pl.program_id(0)]
    base = ki * k_chunk

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # chunk liveness: some key in [base, base+k_chunk) passes the mask
    live = base <= pos
    if window:
        hi = base + k_chunk - 1 > pos - window
        if n_sink:
            hi |= base < n_sink
        live &= hi

    def by_kv_head(fn, shape):
        """Row-select ``fn(h)`` (computed for all H query rows) into the
        rows of KV head h's query group."""
        row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        out = fn(0)
        for h in range(1, KH):
            out = jnp.where((row >= h * G) & (row < (h + 1) * G), fn(h), out)
        return out

    @pl.when(live)
    def _compute():
        q = q_ref[0]  # (H, D)
        kv_lanes = lambda ref, h: ref[0, :, h * D:(h + 1) * D]  # (C, D)
        s = by_kv_head(
            lambda h: mxu_dot(q, kv_lanes(k_ref, h).astype(q.dtype), 1),
            (H, k_chunk),
        ) * scale
        pk = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        ok = pk <= pos
        if window:
            in_w = pk > pos - window
            if n_sink:
                in_w |= pk < n_sink
            ok &= in_w
        s = jnp.where(ok, s, -jnp.inf)
        m_prev = m_ref[...]  # (H, 1)
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(-1, keepdims=True)
        v_dt = v_ref.dtype
        pv = by_kv_head(
            lambda h: mxu_dot(p.astype(v_dt), kv_lanes(v_ref, h), 0), (H, D)
        )
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        o = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)  # (H, D) fp32
        if not proj:
            o_ref[0] = o.astype(o_ref.dtype)
            return
        # paired out-projection, still in VMEM: gather the attended lanes by
        # the frozen [I | J | resid] metadata (one-hot MXU contractions, one
        # per head's D lanes) and contract each column block against its
        # subtractor segments
        o_io = o.astype(io_dtype)
        Bw, bn = km_ref.shape[0], km_ref.shape[2]

        def gather(w, idx_ref, minus_ref=None):
            """(1, n) lanes ``o[idx]`` (minus ``o[minus]``) of block w, in
            the I/O dtype: the subtractor runs at input precision, as in the
            paired GEMM kernel."""
            idx = idx_ref[w:w + 1, :]  # (1, n) lane ids into o's H·D
            lanes = jax.lax.broadcasted_iota(jnp.int32, (D, idx.shape[1]), 0)
            out = None
            for h in range(H):
                sel = (lanes + h * D == idx).astype(io_dtype)
                if minus_ref is not None:
                    sel -= (lanes + h * D == minus_ref[w:w + 1, :]).astype(io_dtype)
                part = mxu_dot(o_io[h:h + 1, :], sel, 0)
                out = part if out is None else out + part
            return out.astype(io_dtype)

        for w in range(Bw):
            y = mxu_dot(gather(w, i_ref, j_ref), km_ref[w].astype(io_dtype), 0)
            y += mxu_dot(gather(w, r_ref), wr_ref[w].astype(io_dtype), 0)
            if has_residual:
                # fused skip connection: fp32 add in VMEM, no standalone add op
                y += resid_ref[0, :, w * bn:(w + 1) * bn].astype(jnp.float32)
            o_ref[0, :, w * bn:(w + 1) * bn] = y.astype(o_ref.dtype)


def _launch(q, k_cache, v_cache, pos, extra_specs, extra_operands, out_cols,
            out_dtype, *, k_chunk, interpret, window, n_sink, proj, has_residual):
    """Shared ``pallas_call`` for the bare and the fused kernel: SMEM
    positions, lane-dense cache chunks, fp32 online-softmax scratch."""
    B, _, H, D = q.shape
    _, S, KH, _ = k_cache.shape
    if H % KH != 0:
        raise ValueError(
            f"GQA requires query heads to divide evenly over kv heads: "
            f"H={H}, KH={KH}"
        )
    k_chunk = min(k_chunk, S)
    nk = -(-S // k_chunk)
    # (B, S, KH, D) → (B, S, KH·D) is a free reshape; pad S to the chunk grid
    k_cache, v_cache = (
        _pad_to(c.reshape(B, S, KH * D), 1, nk * k_chunk) for c in (k_cache, v_cache)
    )
    kernel = functools.partial(
        _decode_attn_kernel,
        scale=1.0 / math.sqrt(D), window=window, n_sink=n_sink,
        k_chunk=k_chunk, nk=nk, KH=KH, G=H // KH, D=D,
        proj=proj, has_residual=has_residual, io_dtype=q.dtype,
    )
    out_shape, out_block = ((B, H, D), (1, H, D)) if out_cols is None else (
        (B, 1, out_cols), (1, 1, out_cols))
    return pl.pallas_call(
        kernel,
        name="fused_attn_decode",
        grid=(B, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, H, D), lambda b, ki: (b, 0, 0)),
            pl.BlockSpec((1, k_chunk, KH * D), lambda b, ki: (b, ki, 0)),
            pl.BlockSpec((1, k_chunk, KH * D), lambda b, ki: (b, ki, 0)),
            *extra_specs,
        ],
        out_specs=pl.BlockSpec(out_block, lambda b, ki: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(out_shape, out_dtype),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(pos.astype(jnp.int32), q[:, 0], k_cache, v_cache, *extra_operands)


def decode_attention_fwd(
    q: jax.Array,  # (B, 1, H, D) one post-rope query row per slot
    k_cache: jax.Array,  # (B, S, KH, D)
    v_cache: jax.Array,
    pos: jax.Array,  # (B,) int32 current position of each slot
    *,
    window: int = 0,
    n_sink: int = 0,
    k_chunk: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Bare fused decode attention: returns the attended (B, 1, H, D) rows.

    Same kernel as :func:`fused_decode_attention` minus the out-projection —
    the parity surface the tests pin against ``layers.decode_attention``.
    """
    out = _launch(
        q, k_cache, v_cache, pos, [], [], None, q.dtype,
        k_chunk=k_chunk, interpret=interpret, window=window, n_sink=n_sink,
        proj=False, has_residual=False,
    )
    return out[:, None]


def fused_decode_attention(
    q: jax.Array,  # (B, 1, H, D)
    k_cache: jax.Array,  # (B, S, KH, D)
    v_cache: jax.Array,
    pos: jax.Array,  # (B,) int32
    idx_i: jax.Array,  # (Bw, Pmax) int32 blocked pair lanes of the out-proj
    idx_j: jax.Array,  # (Bw, Pmax) int32
    idx_r: jax.Array,  # (Bw, Rmax) int32 residual lanes
    kmat: jax.Array,  # (Bw, Pmax, bn) masked pair magnitudes (W[I]-W[J])/2
    w_res: jax.Array,  # (Bw, Rmax, bn) masked residual weights
    residual: jax.Array | None,  # (B, n_cols) fused skip connection
    *,
    n_cols: int,
    out_dtype=None,
    window: int = 0,
    n_sink: int = 0,
    k_chunk: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Decode attention + paired out-projection in one launch → (B, n_cols).

    The attended values live only in VMEM scratch; the single HBM writeback
    per slot is the projected (and residual-added) output row.
    """
    B = q.shape[0]
    Bw, Pmax = idx_i.shape
    Rmax = idx_r.shape[1]
    bn = kmat.shape[-1]
    assert Bw * bn >= n_cols, (Bw, bn, n_cols)
    interpret = resolve_interpret(interpret)
    if not interpret and Bw > 1 and bn % 128:
        # every block's (Pmax, bn) weights sit whole in VMEM, and a block
        # narrower than the 128-lane tile pads 128/bn-fold there
        raise NotImplementedError(
            f"fused decode attention on the TPU needs out-projection pairing "
            f"blocks of a multiple of 128 columns (got pair_block_n={bn} over "
            f"{Bw} blocks): use structured pairing (pair_block_n=0) or "
            f"attn='xla' with column-blocked pairing")
    has_residual = residual is not None
    if out_dtype is None:
        out_dtype = residual.dtype if has_residual else q.dtype
    whole = lambda shape: pl.BlockSpec(shape, lambda b, ki: (0,) * len(shape))
    specs = [whole((Bw, Pmax)), whole((Bw, Pmax)), whole((Bw, Rmax)),
             whole((Bw, Pmax, bn)), whole((Bw, Rmax, bn))]
    operands = [idx_i.astype(jnp.int32), idx_j.astype(jnp.int32),
                idx_r.astype(jnp.int32), kmat, w_res]
    if has_residual:
        specs.append(pl.BlockSpec((1, 1, Bw * bn), lambda b, ki: (b, 0, 0)))
        operands.append(_pad_to(residual, 1, Bw * bn)[:, None])
    out = _launch(
        q, k_cache, v_cache, pos, specs, operands, Bw * bn, out_dtype,
        k_chunk=k_chunk, interpret=interpret, window=window, n_sink=n_sink,
        proj=True, has_residual=has_residual,
    )
    return out[:, 0, :n_cols]
