"""Pallas TPU kernel: fused flash-attention forward (GQA, causal).

The roofline analysis (EXPERIMENTS.md §Roofline) shows every non-SSM cell
memory-bound, dominated by the flash score/probability blocks round-tripping
HBM — XLA cannot fuse across the online-softmax loop, a Pallas kernel is the
mechanism that keeps them in VMEM.  This kernel is the TPU-target
implementation; it is validated in interpret mode in this container and its
VMEM-resident traffic model backs the `attn_fused` accounting in the
dry-run (§Perf iteration 3).

Tiling: grid (B, H, nq, nk) with the KV dimension innermost ("arbitrary" —
sequential), carrying (m, l, acc) in VMEM scratch across the KV iterations of
one q-block; q/k/v/o blocks stream per grid step.  Causal skipping happens
in-kernel via ``pl.when`` (a fully-masked block never touches the MXU).
GQA is handled in the k/v index maps (query head h reads kv head h·KH//H).

Ragged sequence lengths are handled internally: inputs are zero-padded up to
the chunk grid, padded *keys* are masked to -inf in-kernel (mirroring
``layers.flash_attention``'s ``pos_k < Sk`` lane mask), and the output is
sliced back to the caller's (B, Sq, H, D).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.paired_matmul import _pad_to, resolve_interpret


def _flash_fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
    *, scale: float, causal: bool, q_chunk: int, k_chunk: int, nk: int,
    valid_k: int
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # causal bound: the last kv block this q block attends to is skipped
    # statically via pl.when below
    @pl.when((not causal) or (ki * k_chunk <= (qi + 1) * q_chunk - 1))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (q_chunk, D)
        k = k_ref[0, 0].astype(jnp.float32)  # (k_chunk, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (q_chunk, k_chunk)
        ragged = valid_k % k_chunk != 0
        if causal or ragged:
            pos_k = ki * k_chunk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            ok = jnp.full(s.shape, True)
            if causal:
                pos_q = qi * q_chunk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                ok &= pos_k <= pos_q
            if ragged:  # zero-padded key lanes never score
                ok &= pos_k < valid_k
            s = jnp.where(ok, s, -jnp.inf)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(-1))
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[:, None])
        p = jnp.where(jnp.isfinite(s), p, 0.0)
        corr = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
        l_ref[...] = l_ref[...] * corr + p.sum(-1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_fwd(
    q: jax.Array,  # (B, Sq, H, D)
    k: jax.Array,  # (B, Sk, KH, D)
    v: jax.Array,
    *,
    causal: bool = True,
    q_chunk: int = 512,
    k_chunk: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused flash-attention forward. Returns (B, Sq, H, D) in q.dtype."""
    B, Sq, H, D = q.shape
    _, Sk, KH, _ = k.shape
    if H % KH != 0:
        raise ValueError(
            f"GQA requires query heads to divide evenly over kv heads: "
            f"H={H}, KH={KH}"
        )
    G = H // KH
    scale = 1.0 / math.sqrt(D)
    q_chunk = min(q_chunk, Sq)
    k_chunk = min(k_chunk, Sk)
    # Ragged lengths: pad up to the chunk grid with zero lanes.  Padded keys
    # are masked to -inf in-kernel (valid_k); padded query rows compute
    # finite garbage that the final slice drops.
    nq, nk = -(-Sq // q_chunk), -(-Sk // k_chunk)
    Sq_p, Sk_p = nq * q_chunk, nk * k_chunk
    q = _pad_to(q, 1, Sq_p)
    k = _pad_to(k, 1, Sk_p)
    v = _pad_to(v, 1, Sk_p)

    # layout: (B, H, S, D) so blocks are (1, 1, chunk, D)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _flash_fwd_kernel,
        scale=scale, causal=causal, q_chunk=q_chunk, k_chunk=k_chunk, nk=nk,
        valid_k=Sk,
    )
    scratch = [
        pltpu.VMEM((q_chunk,), jnp.float32),
        pltpu.VMEM((q_chunk,), jnp.float32),
        pltpu.VMEM((q_chunk, D), jnp.float32),
    ]
    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, q_chunk, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, k_chunk, D), lambda b, h, qi, ki, _G=G: (b, h // _G, ki, 0)),
            pl.BlockSpec((1, 1, k_chunk, D), lambda b, h, qi, ki, _G=G: (b, h // _G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, q_chunk, D), lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq_p, D), q.dtype),
        scratch_shapes=scratch,
        interpret=resolve_interpret(interpret),
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)[:, :Sq]
