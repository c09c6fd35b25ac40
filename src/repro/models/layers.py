"""Layer zoo shared by all ten assigned architectures.

Everything is a pure function over plain value pytrees (see param.py for the
axes annotations made at init time).  Conventions:

* activations: (batch, seq, d_model) in the config compute dtype (bf16);
* all softmax / normalisation statistics accumulate in fp32;
* attention weights keep heads explicit — (d_model, heads, head_dim) — so the
  "q_heads"/"kv_heads" logical axes are shardable;
* every attention path goes through ``flash_attention`` (blocked online
  softmax — the pure-JAX analogue of the fused TPU kernel, so the lowered
  HLO has the memory profile the roofline analysis assumes) or through
  ``decode_attention`` (single query position against a cache).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.param import Param
from repro.parallel.sharding import constrain

# ---------------------------------------------------------------------------
# initialisers
# ---------------------------------------------------------------------------


def _dense_init(key, shape, axes, scale_dim=0, dtype=jnp.float32) -> Param:
    """Truncated-normal fan-in init annotated with logical axes."""
    fan_in = shape[scale_dim] if isinstance(scale_dim, int) else int(np.prod([shape[i] for i in scale_dim]))
    w = jax.random.truncated_normal(key, -2.0, 2.0, shape, dtype) / math.sqrt(fan_in)
    return Param(w, axes)


def _zeros(shape, axes, dtype=jnp.float32) -> Param:
    return Param(jnp.zeros(shape, dtype), axes)


def _ones(shape, axes, dtype=jnp.float32) -> Param:
    return Param(jnp.ones(shape, dtype), axes)


# ---------------------------------------------------------------------------
# norms / rope / activations
# ---------------------------------------------------------------------------


def init_norm(cfg: ModelConfig, d: int | None = None) -> dict:
    d = d or cfg.d_model
    p = {"scale": _ones((d,), ("embed",))}
    if cfg.norm == "layernorm":
        p["bias"] = _zeros((d,), ("embed",))
    return p


def apply_norm(p: dict, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    if "bias" in p:  # layernorm
        mu = xf.mean(-1, keepdims=True)
        var = ((xf - mu) ** 2).mean(-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:  # rmsnorm
        var = (xf * xf).mean(-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


def rms_head_norm(scale: jax.Array, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    """Per-head RMS norm over head_dim (qwen3 qk_norm). x: (..., head_dim)."""
    xf = x.astype(jnp.float32)
    var = (xf * xf).mean(-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(x.dtype)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding, rotate-half convention.

    x: (B, S, H, D) with D even; positions: (B, S) int32.
    """
    d = x.shape[-1]
    freqs = (theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d))  # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, S, D/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def activation(name: str, x: jax.Array) -> jax.Array:
    return jax.nn.silu(x) if name == "silu" else jax.nn.gelu(x)


def dense(x: jax.Array, w: jax.Array, bias: jax.Array | None = None,
          act: str | None = None, *, pairing: dict | None = None,
          residual: jax.Array | None = None) -> jax.Array:
    """GEMM over the last axis with optional bias + activation + residual.

    The single dispatch point between the XLA einsum path (default) and the
    K-tiled, epilogue-fused Pallas kernels:

    * a :func:`repro.kernels.ops.pallas_paired_gemm` policy
      (``PerfKnobs(gemm="pallas_paired")``) routes any call that carries
      ``pairing`` metadata (``core.transform.pair_lm_params``) through the
      *subtractor* kernel — pair magnitudes recomputed from the live ``w``,
      bias/activation/``residual`` all fused into the single writeback;
    * a :func:`repro.kernels.ops.pallas_gemm` policy
      (``PerfKnobs(gemm="pallas")``) routes the matmul + bias + activation
      through the plain fused kernel;
    * otherwise the XLA einsum path runs (``pairing`` is ignored there: the
      live weights ARE the r=0-exact reference the paired path is tested
      against).

    ``residual`` is an output-shaped skip connection added *after* the
    activation — on the paired path it executes inside the kernel epilogue,
    on the other paths as a plain add, so callers can thread their
    ``h + sublayer(x)`` through unconditionally.
    """
    from repro.kernels import ops as kops

    ppol = kops.current_paired_gemm_policy()
    if pairing is not None and ppol is not None:
        return kops.fused_paired_dense(
            x, w, pairing, bias, activation=act or "none", residual=residual,
            pair_block_n=ppol.pair_block_n,
            block_m=ppol.block_m, block_n=ppol.block_n, block_k=ppol.block_k,
            interpret=ppol.interpret,
        )
    pol = kops.current_gemm_policy()
    if pol is not None:
        y = kops.fused_dense(
            x, w, bias, activation=act or "none",
            block_m=pol.block_m, block_n=pol.block_n, block_k=pol.block_k,
            interpret=pol.interpret,
        )
        return y + residual.astype(y.dtype) if residual is not None else y
    y = jnp.einsum("...d,df->...f", x, w)
    if bias is not None:
        y = y + bias
    if act:
        y = activation(act, y)
    if residual is not None:
        y = y + residual.astype(y.dtype)
    return y


# ---------------------------------------------------------------------------
# flash attention (blocked online softmax, pure JAX)
# ---------------------------------------------------------------------------


def _block_mask(pos_q, pos_k, *, causal: bool, window: int, n_sink: int):
    """(Q, K) bool mask for one (q-block, k-block) pair of position vectors."""
    pq = pos_q[:, None]
    pk = pos_k[None, :]
    ok = jnp.ones(pq.shape[:1] + pk.shape[1:], bool)
    if causal:
        ok = pk <= pq
    if window:
        in_window = pk > pq - window
        if n_sink:
            in_window = in_window | (pk < n_sink)
        ok = ok & in_window
    return ok


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    n_sink: int = 0,
    q_offset: int = 0,
    q_chunk: int = 1024,
    k_chunk: int = 1024,
    causal_skip: bool = True,
) -> jax.Array:
    """Blocked attention with online softmax (fp32 statistics).

    q: (B, Sq, H, D);  k, v: (B, Sk, KH, D) with H = KH * G (GQA).
    Returns (B, Sq, H, D).  Memory high-water mark is one
    (B, KH, G, q_chunk, k_chunk) score block instead of (Sq, Sk).

    ``causal_skip``: for pure-causal attention the q-blocks are unrolled
    (their count is static) and each one scans only the KV blocks at or
    below its causal bound — fully-masked blocks are never computed.
    Halves score-block FLOPs + HBM traffic at Sq == Sk (§Perf iteration 2c).
    Sliding-window/sink cases keep the scanned path with masking.
    """
    B, Sq, H, D = q.shape
    _, Sk, KH, _ = k.shape
    G = H // KH
    scale = 1.0 / math.sqrt(D)

    q_chunk = min(q_chunk, Sq)
    k_chunk = min(k_chunk, Sk)
    nq = -(-Sq // q_chunk)
    nk = -(-Sk // k_chunk)
    pad_q = nq * q_chunk - Sq
    pad_k = nk * k_chunk - Sk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))

    qb = q.reshape(B, nq, q_chunk, KH, G, D).transpose(1, 0, 2, 3, 4, 5)
    kb = k.reshape(B, nk, k_chunk, KH, D).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nk, k_chunk, KH, D).transpose(1, 0, 2, 3, 4)

    pos_q_all = q_offset + jnp.arange(nq * q_chunk, dtype=jnp.int32)
    pos_k_all = jnp.arange(nk * k_chunk, dtype=jnp.int32)

    def q_block(args, nk_hi: int | None = None):
        # Everything inside this scope is what the Pallas kernel
        # (kernels/flash_attention.py) keeps in VMEM on the TPU target; the
        # dry-run's `attn_fused` accounting recognises the scope name.
        with jax.named_scope("flash_vmem"):
            return _q_block_inner(args, nk_hi)

    def _q_block_inner(args, nk_hi):
        qi, qblk = args  # qblk: (B, q_chunk, KH, G, D)
        pos_q = jax.lax.dynamic_slice_in_dim(pos_q_all, qi * q_chunk, q_chunk)

        # NOTE: both loop bodies are checkpointed — without this, reverse-mode
        # through the scan saves every (q_chunk, k_chunk) score/probability
        # block, i.e. O(Sq·Sk) residuals, exactly the quadratic buffer flash
        # attention exists to avoid (observed: +13 GiB/device on train_4k).
        @jax.checkpoint
        def kv_step(carry, kv):
            m, l, acc = carry
            ki, kblk, vblk = kv
            s = jnp.einsum(
                "bqkgd,bckd->bkgqc", qblk, kblk,
                preferred_element_type=jnp.float32,
            ) * scale  # (B, KH, G, q_chunk, k_chunk)
            pos_k = jax.lax.dynamic_slice_in_dim(pos_k_all, ki * k_chunk, k_chunk)
            ok = _block_mask(pos_q, pos_k, causal=causal, window=window, n_sink=n_sink)
            ok = ok & (pos_k < Sk)[None, :]  # padded keys are never attended
            s = jnp.where(ok[None, None, None], s, -jnp.inf)
            m_new = jnp.maximum(m, s.max(-1))
            # guard fully-masked rows (m_new == -inf)
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(s - m_safe[..., None])
            p = jnp.where(ok[None, None, None], p, 0.0)
            corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
            l_new = l * corr + p.sum(-1)
            pv = jnp.einsum(
                "bkgqc,bckd->bkgqd", p.astype(vblk.dtype), vblk,
                preferred_element_type=jnp.float32,
            )
            acc_new = acc * corr[..., None] + pv
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, KH, G, q_chunk), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, KH, G, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, KH, G, q_chunk, D), jnp.float32)
        lim = nk if nk_hi is None else nk_hi
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), (jnp.arange(lim), kb[:lim], vb[:lim])
        )
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.transpose(0, 3, 1, 2, 4)  # (B, q_chunk, KH, G, D)

    if causal and causal_skip and not window and Sq > q_chunk:
        # static unroll: q-block i only ever sees KV blocks up to its causal
        # bound — fully-masked blocks are never lowered at all.
        blocks = []
        for qi in range(nq):
            hi = min(nk, -(-(q_offset + (qi + 1) * q_chunk) // k_chunk))
            blocks.append(
                jax.checkpoint(lambda a, _hi=hi: q_block(a, _hi))(
                    (jnp.int32(qi), qb[qi])
                )
            )
        out = jnp.stack(blocks)  # (nq, B, q_chunk, KH, G, D)
    else:
        out = jax.lax.map(jax.checkpoint(q_block), (jnp.arange(nq), qb))
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(B, nq * q_chunk, H, D)
    return out[:, :Sq].astype(q.dtype)


def decode_attention(
    q: jax.Array,  # (B, 1, H, D)
    k_cache: jax.Array,  # (B, S, KH, D)
    v_cache: jax.Array,
    pos: jax.Array,  # (B,) current position of the new token
    *,
    window: int = 0,
    n_sink: int = 0,
) -> jax.Array:
    """Single-token attention against a (possibly longer) cache."""
    B, _, H, D = q.shape
    _, S, KH, _ = k_cache.shape
    G = H // KH
    scale = 1.0 / math.sqrt(D)
    qg = q.reshape(B, KH, G, D)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache, preferred_element_type=jnp.float32) * scale
    pk = jnp.arange(S, dtype=jnp.int32)[None, :]  # (1, S)
    ok = pk <= pos[:, None]
    if window:
        in_w = pk > (pos[:, None] - window)
        if n_sink:
            in_w = in_w | (pk < n_sink)
        ok = ok & in_w
    s = jnp.where(ok[:, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, 1, H, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA attention block
# ---------------------------------------------------------------------------


def init_attention(cfg: ModelConfig, key) -> dict:
    d, H, KH, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": _dense_init(ks[0], (d, H, hd), ("embed", "q_heads", "head_dim")),
        "wk": _dense_init(ks[1], (d, KH, hd), ("embed", "kv_heads", "head_dim")),
        "wv": _dense_init(ks[2], (d, KH, hd), ("embed", "kv_heads", "head_dim")),
        "wo": _dense_init(ks[3], (H, hd, d), ("q_heads", "head_dim", "embed"), scale_dim=(0, 1)),
    }
    if cfg.qkv_bias:
        p["bq"] = _zeros((H, hd), ("q_heads", "head_dim"))
        p["bk"] = _zeros((KH, hd), ("kv_heads", "head_dim"))
        p["bv"] = _zeros((KH, hd), ("kv_heads", "head_dim"))
    if cfg.qk_norm:
        p["q_norm"] = _ones((hd,), ("head_dim",))
        p["k_norm"] = _ones((hd,), ("head_dim",))
    return p


def _qkv_post(cfg: ModelConfig, p: dict, q, k, v, positions: jax.Array):
    """Bias / qk-norm / rope applied to freshly projected (…, heads, hd)."""
    cdt = q.dtype
    if cfg.qkv_bias:
        q = q + p["bq"].astype(cdt)
        k = k + p["bk"].astype(cdt)
        v = v + p["bv"].astype(cdt)
    if cfg.qk_norm:
        q = rms_head_norm(p["q_norm"], q)
        k = rms_head_norm(p["k_norm"], k)
    if cfg.rope_theta:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def _qkv(cfg: ModelConfig, p: dict, x: jax.Array, positions: jax.Array):
    cdt = x.dtype
    d = x.shape[-1]

    def proj(name):
        # flattened-head GEMM view so the projection goes through `dense`
        # (and with it the paired-kernel policy, when `name`'s weight
        # carries pair_lm_params metadata)
        w = p[name].astype(cdt)
        heads, hd = w.shape[-2], w.shape[-1]
        y = dense(x, w.reshape(d, heads * hd),
                  pairing=p.get(name + "_pairing"))
        return y.reshape(*x.shape[:-1], heads, hd)

    q, k, v = proj("wq"), proj("wk"), proj("wv")
    return _qkv_post(cfg, p, q, k, v, positions)


def _fused_qkv_proj(p: dict, x: jax.Array, ppol):
    """All three QKV projections as ONE subtractor launch, when possible.

    The q/k/v weights concatenate along their output columns and their
    *blocked* pairing metadata concatenates along the block axis (lane lists
    pad to a common Pmax/Rmax with masked zero lanes — exact, the zero-lane
    trick), so a single :func:`repro.kernels.ops.fused_paired_dense` call
    projects all three.  Requires every weight to carry 2-D blocked metadata
    and the block size to divide the wq/wv column counts so block boundaries
    stay on weight boundaries (always true per-column, ``pair_block_n=1``).
    Returns ``(q, k, v)`` shaped ``(…, heads, hd)`` or None when the layout
    doesn't allow the concatenation (caller falls back to per-weight calls).
    """
    names = ("wq", "wk", "wv")
    metas = [p.get(n + "_pairing") for n in names]
    if any(m is None or m["I"].ndim != 2 for m in metas):
        return None
    bn = ppol.pair_block_n
    d = x.shape[-1]
    ws = [p[n].astype(x.dtype).reshape(d, -1) for n in names]
    ns = [w.shape[1] for w in ws]
    if bn < 1 or ns[0] % bn or ns[1] % bn:
        return None
    pmax = max(m["I"].shape[1] for m in metas)
    rmax = max(m["resid"].shape[1] for m in metas)
    pad = lambda a, n: jnp.pad(a, ((0, 0), (0, n - a.shape[1])))
    meta = {
        key: jnp.concatenate(
            [pad(m[key], pmax if key in ("I", "J", "pair_mask") else rmax)
             for m in metas], axis=0)
        for key in ("I", "J", "pair_mask", "resid", "resid_mask")
    }
    from repro.kernels import ops as kops

    y = kops.fused_paired_dense(
        x, jnp.concatenate(ws, axis=1), meta,
        pair_block_n=bn, block_m=ppol.block_m, block_n=ppol.block_n,
        block_k=ppol.block_k, interpret=ppol.interpret,
    )
    yq, yk, yv = jnp.split(y, [ns[0], ns[0] + ns[1]], axis=-1)
    shape = lambda arr, n: arr.reshape(*x.shape[:-1], *p[n].shape[-2:])
    return shape(yq, "wq"), shape(yk, "wk"), shape(yv, "wv")


def attn_out_proj(p: dict, out: jax.Array,
                  residual: jax.Array | None = None) -> jax.Array:
    """Attention output projection through `dense`, flattened-head view.

    ``residual`` is the sublayer's skip connection (the pre-attention
    hidden state): under the paired-GEMM policy it fuses into the kernel's
    residual-add epilogue — the decoder's ``h + attn(x)`` stops being a
    standalone add — and on the XLA path it is the same plain add as
    before.
    """
    cdt = out.dtype
    wo = p["wo"].astype(cdt)
    H, hd, d = wo.shape
    o2 = out.reshape(*out.shape[:-2], H * hd)
    return dense(o2, wo.reshape(H * hd, d),
                 pairing=p.get("wo_pairing"), residual=residual)


def attention_block(
    cfg: ModelConfig,
    p: dict,
    x: jax.Array,
    positions: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    n_sink: int = 0,
    q_chunk: int = 1024,
    k_chunk: int = 1024,
) -> jax.Array:
    """Full attention sublayer (projections + flash attention + out proj)."""
    q, k, v = _qkv(cfg, p, x, positions)
    q = constrain(q, "batch", None, "q_heads", None)
    k = constrain(k, "batch", None, "kv_heads", None)
    v = constrain(v, "batch", None, "kv_heads", None)
    out = flash_attention(
        q, k, v, causal=causal, window=window, n_sink=n_sink,
        q_chunk=q_chunk, k_chunk=k_chunk,
    )
    return attn_out_proj(p, out)


@jax.named_scope("cache_update")
def _cache_write(cache: dict, k: jax.Array, v: jax.Array, pos: jax.Array) -> dict:
    """The new token's K and V (``(B, 1, KH, hd)``) written into row ``pos``
    of each slot's cache."""
    bidx = jnp.arange(k.shape[0])
    return {"k": cache["k"].at[bidx, pos].set(k[:, 0].astype(cache["k"].dtype)),
            "v": cache["v"].at[bidx, pos].set(v[:, 0].astype(cache["v"].dtype))}


def attention_decode_block(
    cfg: ModelConfig,
    p: dict,
    x: jax.Array,  # (B, 1, d)
    cache: dict,  # {"k": (B, S, KH, hd), "v": ...}
    pos: jax.Array,  # (B,)
    *,
    window: int = 0,
    n_sink: int = 0,
    residual: jax.Array | None = None,
) -> tuple[jax.Array, dict]:
    from repro.kernels import ops as kops

    apol = kops.current_attn_policy()
    if apol is None or x.shape[1] != 1:
        q, k, v = _qkv(cfg, p, x, pos[:, None])
        new = _cache_write(cache, k, v, pos)
        out = decode_attention(q, new["k"], new["v"], pos, window=window, n_sink=n_sink)
        y = attn_out_proj(p, out, residual=residual)
        return y, new

    # fused decode path (PerfKnobs(attn="pallas_fused")): one subtractor
    # launch projects q|k|v together when the blocked metadata concatenates,
    # then one kernel runs attention + the paired out-projection + the
    # sublayer residual — the attended values never round-trip HBM between
    # the attention and the out-projection (kernels/decode_attention.py).
    ppol = kops.current_paired_gemm_policy()
    qkv = _fused_qkv_proj(p, x, ppol) if ppol is not None else None
    if qkv is None:
        q, k, v = _qkv(cfg, p, x, pos[:, None])
    else:
        q, k, v = _qkv_post(cfg, p, *qkv, pos[:, None])
    new = _cache_write(cache, k, v, pos)
    wo = p["wo"].astype(x.dtype)
    H, hd, d = wo.shape
    meta = p.get("wo_pairing") if ppol is not None else None
    y = kops.fused_attn_decode(
        q, new["k"], new["v"], pos, wo.reshape(H * hd, d), meta,
        residual=residual,
        pair_block_n=ppol.pair_block_n if ppol is not None else 0,
        window=window, n_sink=n_sink,
        k_chunk=apol.k_chunk, interpret=apol.interpret,
    )
    return y, new


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2 multi-head latent attention)
# ---------------------------------------------------------------------------


def init_mla(cfg: ModelConfig, key) -> dict:
    m = cfg.mla
    d, H = cfg.d_model, cfg.n_heads
    ks = jax.random.split(key, 6)
    return {
        "wq": _dense_init(ks[0], (d, H, m.qk_nope_dim + m.qk_rope_dim), ("embed", "q_heads", "head_dim")),
        "w_dkv": _dense_init(ks[1], (d, m.kv_lora_rank), ("embed", "kv_lora")),
        "w_kr": _dense_init(ks[2], (d, m.qk_rope_dim), ("embed", "head_dim")),
        "w_uk": _dense_init(ks[3], (m.kv_lora_rank, H, m.qk_nope_dim), ("kv_lora", "q_heads", "head_dim")),
        "w_uv": _dense_init(ks[4], (m.kv_lora_rank, H, m.v_head_dim), ("kv_lora", "q_heads", "head_dim")),
        "wo": _dense_init(ks[5], (H, m.v_head_dim, d), ("q_heads", "head_dim", "embed"), scale_dim=(0, 1)),
        "kv_norm": _ones((m.kv_lora_rank,), ("kv_lora",)),
    }


def mla_block(
    cfg: ModelConfig,
    p: dict,
    x: jax.Array,
    positions: jax.Array,
    *,
    q_chunk: int = 1024,
    k_chunk: int = 1024,
) -> jax.Array:
    """Training/prefill MLA: materialise per-head K/V from the latent.

    The down-projections (wq/w_dkv/w_kr) and the out-projection route
    through `dense` in the flattened-head view — onto the subtractor kernel
    when their weights carry pair_params metadata — while the latent
    up-projections (w_uk/w_uv) stay as einsums (absorbed-matrix form)."""
    m = cfg.mla
    cdt = x.dtype
    d = x.shape[-1]
    H = cfg.n_heads
    qk = m.qk_nope_dim + m.qk_rope_dim
    wq = p["wq"].astype(cdt)
    q = dense(x, wq.reshape(d, H * qk),
              pairing=p.get("wq_pairing")).reshape(*x.shape[:-1], H, qk)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_dim], axis=-1)
    q_rope = rope(q_rope, positions, cfg.rope_theta)

    c_kv = dense(x, p["w_dkv"].astype(cdt), pairing=p.get("w_dkv_pairing"))
    c_kv = rms_head_norm(p["kv_norm"], c_kv)
    k_rope = dense(x, p["w_kr"].astype(cdt), pairing=p.get("w_kr_pairing"))
    k_rope = rope(k_rope[:, :, None, :], positions, cfg.rope_theta)  # (B,S,1,rope)

    k_nope = jnp.einsum("bsr,rhk->bshk", c_kv, p["w_uk"].astype(cdt))
    v = jnp.einsum("bsr,rhk->bshk", c_kv, p["w_uv"].astype(cdt))

    H = cfg.n_heads
    k_rope_b = jnp.broadcast_to(k_rope, k_rope.shape[:2] + (H, m.qk_rope_dim))
    qc = jnp.concatenate([q_nope, q_rope], axis=-1)
    kc = jnp.concatenate([k_nope, k_rope_b], axis=-1)
    # pad v to qk head dim so flash kernel shapes line up, then slice back
    out = flash_attention(qc, kc, jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, qc.shape[-1] - v.shape[-1]))),
                          causal=True, q_chunk=q_chunk, k_chunk=k_chunk)
    out = out[..., : m.v_head_dim]
    return dense(out.reshape(*out.shape[:-2], H * m.v_head_dim),
                 p["wo"].astype(cdt).reshape(H * m.v_head_dim, d),
                 pairing=p.get("wo_pairing"))


def mla_decode_block(
    cfg: ModelConfig,
    p: dict,
    x: jax.Array,  # (B, 1, d)
    cache: dict,  # {"c_kv": (B, S, R), "k_rope": (B, S, rope)}
    pos: jax.Array,
) -> tuple[jax.Array, dict]:
    """Absorbed-matrix MLA decode: attention runs directly in the compressed
    latent space — the cache stores only (c_kv, k_rope), the paper's memory
    saving — W_uk is folded into the query and W_uv into the output."""
    m = cfg.mla
    cdt = x.dtype
    B = x.shape[0]
    d = x.shape[-1]
    H = cfg.n_heads
    qk = m.qk_nope_dim + m.qk_rope_dim
    q = dense(x, p["wq"].astype(cdt).reshape(d, H * qk),
              pairing=p.get("wq_pairing")).reshape(*x.shape[:-1], H, qk)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_dim], axis=-1)
    q_rope = rope(q_rope, pos[:, None], cfg.rope_theta)

    c_new = dense(x, p["w_dkv"].astype(cdt), pairing=p.get("w_dkv_pairing"))
    c_new = rms_head_norm(p["kv_norm"], c_new)
    kr_new = rope(
        dense(x, p["w_kr"].astype(cdt), pairing=p.get("w_kr_pairing"))[:, :, None, :],
        pos[:, None], cfg.rope_theta,
    )[:, :, 0, :]

    bidx = jnp.arange(B)
    c_kv = cache["c_kv"].at[bidx, pos].set(c_new[:, 0].astype(cache["c_kv"].dtype))
    k_rope = cache["k_rope"].at[bidx, pos].set(kr_new[:, 0].astype(cache["k_rope"].dtype))

    # absorb W_uk: q_lat (B, H, R) = q_nope @ W_uk^T
    q_lat = jnp.einsum("bhk,rhk->bhr", q_nope[:, 0], p["w_uk"].astype(cdt))
    s = jnp.einsum("bhr,bsr->bhs", q_lat, c_kv, preferred_element_type=jnp.float32)
    s = s + jnp.einsum("bhk,bsk->bhs", q_rope[:, 0], k_rope, preferred_element_type=jnp.float32)
    s = s / math.sqrt(m.qk_nope_dim + m.qk_rope_dim)
    S = c_kv.shape[1]
    ok = jnp.arange(S, dtype=jnp.int32)[None, :] <= pos[:, None]
    s = jnp.where(ok[:, None], s, -jnp.inf)
    pr = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhs,bsr->bhr", pr.astype(cdt), c_kv, preferred_element_type=jnp.float32).astype(cdt)
    # absorb W_uv into the output projection
    out = jnp.einsum("bhr,rhk->bhk", o_lat, p["w_uv"].astype(cdt))
    y = dense(out.reshape(B, H * m.v_head_dim),
              p["wo"].astype(cdt).reshape(H * m.v_head_dim, d),
              pairing=p.get("wo_pairing"))
    return y[:, None], {"c_kv": c_kv, "k_rope": k_rope}


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def init_mlp(cfg: ModelConfig, key, d_ff: int | None = None) -> dict:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    return {
        "w_gate": _dense_init(ks[0], (d, f), ("embed", "ff")),
        "w_up": _dense_init(ks[1], (d, f), ("embed", "ff")),
        "w_down": _dense_init(ks[2], (f, d), ("ff", "embed")),
    }


def mlp_block(cfg: ModelConfig, p: dict, x: jax.Array,
              residual: jax.Array | None = None) -> jax.Array:
    """Gated MLP; ``residual`` fuses the sublayer skip connection into the
    down-projection (kernel epilogue under the paired policy, plain add
    otherwise)."""
    cdt = x.dtype
    g = dense(x, p["w_gate"].astype(cdt), act=cfg.act,
              pairing=p.get("w_gate_pairing"))
    u = dense(x, p["w_up"].astype(cdt), pairing=p.get("w_up_pairing"))
    h = constrain(g * u, "batch", None, "ff")
    return dense(h, p["w_down"].astype(cdt),
                 pairing=p.get("w_down_pairing"), residual=residual)


# ---------------------------------------------------------------------------
# MoE (GShard-style top-k with capacity, sort-based dispatch)
# ---------------------------------------------------------------------------


def init_moe(cfg: ModelConfig, key) -> dict:
    mo = cfg.moe
    d, E, F = cfg.d_model, mo.n_experts, mo.d_ff_expert
    ks = jax.random.split(key, 5)
    p = {
        "router": _dense_init(ks[0], (d, E), ("embed", "experts")),
        "w_gate": _dense_init(ks[1], (E, d, F), ("experts", "embed", "expert_ff"), scale_dim=1),
        "w_up": _dense_init(ks[2], (E, d, F), ("experts", "embed", "expert_ff"), scale_dim=1),
        "w_down": _dense_init(ks[3], (E, F, d), ("experts", "expert_ff", "embed"), scale_dim=1),
    }
    if mo.n_shared:
        sub = jax.random.split(ks[4], 3)
        fs = F * mo.n_shared
        p["shared"] = {
            "w_gate": _dense_init(sub[0], (d, fs), ("embed", "ff")),
            "w_up": _dense_init(sub[1], (d, fs), ("embed", "ff")),
            "w_down": _dense_init(sub[2], (fs, d), ("ff", "embed")),
        }
    return p


def _moe_route(cfg, x, topi, topw):
    """Per-sequence routing: buffers + inverse maps. All ops carry the batch
    dim (per-sequence capacity), so nothing ever crosses sequences."""
    mo = cfg.moe
    B, S, d = x.shape
    E, K = mo.n_experts, mo.top_k
    C = max(1, int(math.ceil(S * K / E * mo.capacity_factor)))
    SK = S * K
    cdt = x.dtype
    bidx = jnp.arange(B, dtype=jnp.int32)[:, None]
    flat_e = topi.reshape(B, SK)
    sort_idx = jnp.argsort(flat_e, axis=-1).astype(jnp.int32)
    sorted_e = jnp.take_along_axis(flat_e, sort_idx, axis=-1)
    counts = jnp.zeros((B, E), jnp.int32).at[bidx, flat_e].add(1)
    starts = jnp.cumsum(counts, axis=-1) - counts
    pos_in_e = (
        jnp.arange(SK, dtype=jnp.int32)[None]
        - jnp.take_along_axis(starts, sorted_e, axis=-1)
    )
    keep = pos_in_e < C
    tok_of = (sort_idx // K).astype(jnp.int32)
    buf_slot = jnp.where(keep, sorted_e * C + pos_in_e, E * C)

    gathered = jnp.take_along_axis(x, tok_of[..., None], axis=1)
    xb = jnp.zeros((B, E * C + 1, d), cdt).at[bidx, buf_slot].set(gathered)
    xb = xb[:, : E * C].reshape(B, E, C, d)

    w_sorted = jnp.take_along_axis(topw.reshape(B, SK), sort_idx, axis=-1)
    inv_tok = jnp.full((B, E * C + 1), S, jnp.int32).at[bidx, buf_slot].set(tok_of)
    inv_w = jnp.zeros((B, E * C + 1), jnp.float32).at[bidx, buf_slot].set(
        w_sorted * keep
    )
    return xb, inv_tok[:, : E * C], inv_w[:, : E * C], counts, C


def _moe_combine(B, S, d, yb, inv_tok, inv_w, cdt):
    bidx = jnp.arange(B, dtype=jnp.int32)[:, None]
    EC = yb.shape[1] * yb.shape[2]
    contrib = yb.reshape(B, EC, d) * inv_w[..., None].astype(cdt)
    y2 = jnp.zeros((B, S + 1, d), cdt).at[bidx, inv_tok].add(contrib)
    return y2[:, :S]


def moe_block(cfg: ModelConfig, p: dict, x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Returns (output, aux_loss).

    Three dispatch paths, most-specific first:

    1. **shard_map** (a mesh with a model axis is active and E divides it) —
       the production path.  Routing runs replicated within each data row
       (it is cheap integer work), every model rank slices *its own* experts'
       buffers out of the local dispatch, computes its expert FFNs, scatters
       its partial outputs, and one psum over `model` closes the combine.
       No all-to-all is needed because activations are batch-sharded over
       `data` only (model ranks in a data row hold identical tokens).  This
       exists because the pjit-visible scatter formulation below makes XLA's
       SPMD partitioner replicate the dispatch buffers (observed on
       deepseek/train_4k: 328 GiB/device and 371 s of collective time —
       EXPERIMENTS.md §Perf).
    2. **dense** (T·K ≤ 2E, i.e. decode) — run every expert on every token;
       no capacity drops, dispatch overhead would dominate the tiny GEMMs.
    3. **pjit scatter** fallback (no mesh, e.g. smoke tests) — per-sequence
       sort-based dispatch.

    Tokens over per-sequence capacity are dropped (standard GShard
    trade-off)."""
    from repro.kernels import ops as kops

    mo = cfg.moe
    B, S, d = x.shape
    T = B * S
    E, K = mo.n_experts, mo.top_k
    cdt = x.dtype

    # Expert GEMMs route through the subtractor kernel when pair_params
    # metadata is attached and a paired policy is active — experts map onto
    # the blocked kernel's column-block grid (shard_map path stays unpaired:
    # its per-rank expert slices would need per-rank metadata slicing).
    ppol = kops.current_paired_gemm_policy()
    paired = ppol is not None and "w_gate_pairing" in p
    ekw = dict(
        pair_block_n=ppol.pair_block_n, block_m=ppol.block_m,
        block_k=ppol.block_k, interpret=ppol.interpret,
    ) if paired else {}

    x2 = x.reshape(T, d)
    logits = jnp.einsum("td,de->te", x2.astype(jnp.float32), p["router"].astype(jnp.float32))
    gates = jax.nn.softmax(logits, axis=-1)  # (T, E)
    topw, topi = jax.lax.top_k(gates, K)  # (T, K)
    topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)

    if T * K <= 2 * E:
        # decode / tiny-batch path: run every expert densely — no capacity,
        # no token drops (what serving engines do for single-token steps,
        # where dispatch overhead would dominate the tiny GEMMs).
        if paired:
            g = kops.fused_paired_expert_dense(
                x2, p["w_gate"].astype(cdt), p["w_gate_pairing"],
                activation=cfg.act, **ekw)
            u = kops.fused_paired_expert_dense(
                x2, p["w_up"].astype(cdt), p["w_up_pairing"], **ekw)
            y_all = kops.fused_paired_expert_dense(
                jnp.moveaxis(g * u, 1, 0), p["w_down"].astype(cdt),
                p["w_down_pairing"], x_per_expert=True, **ekw)
        else:
            g = activation(cfg.act, jnp.einsum("td,edf->tef", x2, p["w_gate"].astype(cdt)))
            u = jnp.einsum("td,edf->tef", x2, p["w_up"].astype(cdt))
            y_all = jnp.einsum("tef,efd->ted", g * u, p["w_down"].astype(cdt))
        w_full = jnp.zeros((T, E), cdt)
        w_full = w_full.at[jnp.arange(T)[:, None], topi].set(topw.astype(cdt))
        y2 = jnp.einsum("ted,te->td", y_all, w_full)
        if mo.n_shared:
            sh = p["shared"]
            gs = dense(x2, sh["w_gate"].astype(cdt), act=cfg.act,
                       pairing=sh.get("w_gate_pairing"))
            us = dense(x2, sh["w_up"].astype(cdt), pairing=sh.get("w_up_pairing"))
            y2 = y2 + dense(gs * us, sh["w_down"].astype(cdt),
                            pairing=sh.get("w_down_pairing"))
        return y2.reshape(B, S, d), jnp.float32(0.0)

    # ---- choose the expert-compute path ------------------------------------
    from repro.parallel.sharding import current as _current_mesh_rules

    mesh, rules = _current_mesh_rules()
    model_axis = rules.mesh_axes("experts") if rules else None
    use_shard_map = (
        mesh is not None
        and isinstance(model_axis, str)
        and model_axis in mesh.axis_names
        and E % mesh.shape[model_axis] == 0
    )

    if use_shard_map:
        y2, counts = _moe_shard_map(cfg, p, x, topi, topw, mesh, rules, model_axis)
    else:
        x = constrain(x, "batch", None, None)
        xb, inv_tok, inv_w, counts, C = _moe_route(cfg, x, topi, topw)
        xb = constrain(xb, "batch", "experts", None, None)
        if paired:
            # experts-as-column-blocks: flatten the (B, C) token dims so every
            # expert's buffer is one row block of the blocked subtractor GEMM
            xe = xb.transpose(1, 0, 2, 3).reshape(E, B * C, d)
            g = kops.fused_paired_expert_dense(
                xe, p["w_gate"].astype(cdt), p["w_gate_pairing"],
                activation=cfg.act, x_per_expert=True, **ekw)
            u = kops.fused_paired_expert_dense(
                xe, p["w_up"].astype(cdt), p["w_up_pairing"],
                x_per_expert=True, **ekw)
            yb2 = kops.fused_paired_expert_dense(
                jnp.moveaxis(g * u, 1, 0), p["w_down"].astype(cdt),
                p["w_down_pairing"], x_per_expert=True, **ekw)
            yb = yb2.reshape(B, C, E, d).transpose(0, 2, 1, 3)
        else:
            g = activation(cfg.act, jnp.einsum("becd,edf->becf", xb, p["w_gate"].astype(cdt)))
            u = jnp.einsum("becd,edf->becf", xb, p["w_up"].astype(cdt))
            yb = jnp.einsum("becf,efd->becd", g * u, p["w_down"].astype(cdt))
        yb = constrain(yb, "batch", "experts", None, None)
        y2 = _moe_combine(B, S, d, yb, inv_tok, inv_w, cdt)
        counts = counts.sum(0)

    if mo.n_shared:
        sh = p["shared"]
        x3 = x.reshape(T, d)
        gs = dense(x3, sh["w_gate"].astype(cdt), act=cfg.act,
                   pairing=sh.get("w_gate_pairing"))
        us = dense(x3, sh["w_up"].astype(cdt), pairing=sh.get("w_up_pairing"))
        y2 = y2 + dense(gs * us, sh["w_down"].astype(cdt),
                        pairing=sh.get("w_down_pairing")).reshape(B, S, d)

    # ---- load-balance aux loss (Switch-style) -------------------------------
    me = gates.mean(0)  # mean router prob per expert
    ce = counts.astype(jnp.float32) / max(T * K, 1)  # dispatched fraction
    aux = (me * ce).sum() * (E * mo.router_aux_weight)

    return y2, aux


def _moe_shard_map(cfg, p, x, topi, topw, mesh, rules, model_axis):
    """Explicit-collective MoE: see moe_block docstring, path (1)."""
    from jax.sharding import PartitionSpec as P
    from jax.experimental.shard_map import shard_map

    mo = cfg.moe
    B, S, d = x.shape
    E = mo.n_experts
    n_model = mesh.shape[model_axis]
    E_loc = E // n_model
    cdt = x.dtype
    batch_axes = rules.mesh_axes("batch")

    def body(x_l, topi_l, topw_l, wg_l, wu_l, wd_l):
        # x_l: (B_loc, S, d) — identical on every model rank of a data row.
        Bl = x_l.shape[0]
        xb, inv_tok, inv_w, counts, C = _moe_route(cfg, x_l, topi_l, topw_l)
        # my experts only
        e0 = jax.lax.axis_index(model_axis) * E_loc
        xb_mine = jax.lax.dynamic_slice_in_dim(xb, e0, E_loc, axis=1)
        g = activation(cfg.act, jnp.einsum("becd,edf->becf", xb_mine, wg_l.astype(cdt)))
        u = jnp.einsum("becd,edf->becf", xb_mine, wu_l.astype(cdt))
        yb = jnp.einsum("becf,efd->becd", g * u, wd_l.astype(cdt))
        # partial combine over my experts, then close the sum over `model`
        inv_tok_m = jax.lax.dynamic_slice_in_dim(
            inv_tok.reshape(Bl, E, C), e0, E_loc, axis=1
        ).reshape(Bl, E_loc * C)
        inv_w_m = jax.lax.dynamic_slice_in_dim(
            inv_w.reshape(Bl, E, C), e0, E_loc, axis=1
        ).reshape(Bl, E_loc * C)
        y2 = _moe_combine(Bl, S, d, yb, inv_tok_m, inv_w_m, cdt)
        y2 = jax.lax.psum(y2, model_axis)
        # (E,) global dispatch counts: sum local batch, then across data rows
        # (model peers hold identical counts, so no psum over model)
        counts = jax.lax.psum(counts.sum(0), batch_axes)
        return y2, counts

    bspec = P(batch_axes, None, None)
    y2, counts = shard_map(
        body,
        mesh=mesh,
        in_specs=(
            bspec,
            P(batch_axes, None, None),
            P(batch_axes, None, None),
            P(model_axis, None, None),
            P(model_axis, None, None),
            P(model_axis, None, None),
        ),
        out_specs=(bspec, P()),
        check_rep=False,
    )(
        x,
        topi.reshape(B, S, -1),
        topw.reshape(B, S, -1).astype(jnp.float32),
        p["w_gate"],
        p["w_up"],
        p["w_down"],
    )
    return y2, counts


# ---------------------------------------------------------------------------
# Mamba-2 (SSD) block
# ---------------------------------------------------------------------------


def init_ssm(cfg: ModelConfig, key) -> dict:
    s = cfg.ssm
    d = cfg.d_model
    d_in = s.expand * d
    H = d_in // s.head_dim
    GN = s.n_groups * s.d_state
    ks = jax.random.split(key, 8)
    # dt bias: softplus^-1 of dt sampled log-uniform in [dt_min, dt_max]
    u = jax.random.uniform(ks[6], (H,))
    dt0 = jnp.exp(u * (math.log(s.dt_max) - math.log(s.dt_min)) + math.log(s.dt_min))
    dt_bias = dt0 + jnp.log(-jnp.expm1(-dt0))
    return {
        "w_z": _dense_init(ks[0], (d, d_in), ("embed", "ssm_in")),
        "w_x": _dense_init(ks[1], (d, d_in), ("embed", "ssm_in")),
        "w_B": _dense_init(ks[2], (d, GN), ("embed", "ssm_state")),
        "w_C": _dense_init(ks[3], (d, GN), ("embed", "ssm_state")),
        "w_dt": _dense_init(ks[4], (d, H), ("embed", "ssm_heads")),
        "conv_x": Param(
            jax.random.normal(ks[5], (s.conv_width, d_in)) / math.sqrt(s.conv_width),
            ("conv", "ssm_in"),
        ),
        "conv_B": Param(jnp.zeros((s.conv_width, GN)).at[-1].set(1.0), ("conv", "ssm_state")),
        "conv_C": Param(jnp.zeros((s.conv_width, GN)).at[-1].set(1.0), ("conv", "ssm_state")),
        "A_log": Param(jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32)), ("ssm_heads",)),
        "D": _ones((H,), ("ssm_heads",)),
        "dt_bias": Param(dt_bias, ("ssm_heads",)),
        "norm": _ones((d_in,), ("ssm_in",)),
        "w_out": _dense_init(ks[7], (d_in, d), ("ssm_in", "embed")),
    }


def _causal_conv(x: jax.Array, w: jax.Array) -> jax.Array:
    """Depthwise causal conv along seq. x: (B, S, C); w: (W, C)."""
    W = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    out = sum(
        xp[:, i : i + x.shape[1]] * w[i][None, None, :] for i in range(W)
    )
    return jax.nn.silu(out)


def _segsum_decay(dA_chunk: jax.Array) -> jax.Array:
    """Lower-triangular decay matrix L[q, t] = exp(sum_{t<i<=q} dA_i).

    dA_chunk: (..., Q). Returns (..., Q, Q) with zeros above the diagonal.
    """
    Q = dA_chunk.shape[-1]
    cs = jnp.cumsum(dA_chunk, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]  # sum over (t, q]
    mask = jnp.tril(jnp.ones((Q, Q), bool))
    return jnp.where(mask, jnp.exp(diff), 0.0)


def ssd_scan(
    x: jax.Array,  # (B, S, H, P)
    dt: jax.Array,  # (B, S, H) (already softplus'd, positive)
    A: jax.Array,  # (H,) negative
    B_: jax.Array,  # (B, S, G, N)
    C_: jax.Array,  # (B, S, G, N)
    *,
    chunk: int,
    h0: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Chunked SSD (Mamba-2 Listing 1, matmul form): returns (y, h_final).

    y: (B, S, H, P); h_final: (B, H, P, N).
    """
    Bb, S, H, P = x.shape
    G, N = B_.shape[2], B_.shape[3]
    rep = H // G
    nc = -(-S // chunk)
    pad = nc * chunk - S
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B_ = jnp.pad(B_, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C_ = jnp.pad(C_, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Q = chunk

    xc = x.reshape(Bb, nc, Q, H, P)
    dtc = dt.reshape(Bb, nc, Q, H).astype(jnp.float32)
    Bc = B_.reshape(Bb, nc, Q, G, N)
    Cc = C_.reshape(Bb, nc, Q, G, N)

    dA = dtc * A  # (B, nc, Q, H) negative
    cs = jnp.cumsum(dA, axis=2)  # within-chunk cumulative

    # ---- intra-chunk (diagonal blocks) --------------------------------------
    # scores[b,c,g,q,t] = C[q]·B[t]  (group-shared)
    scores = jnp.einsum("bcqgn,bctgn->bcgqt", Cc, Bc, preferred_element_type=jnp.float32)
    L = _segsum_decay(dA.transpose(0, 1, 3, 2))  # (B, nc, H, Q, Q)
    # group-shared scores broadcast over heads within a group
    Lg = L.reshape(Bb, nc, G, rep, Q, Q)
    sg = scores[:, :, :, None]  # (B, nc, G, 1, Q, Q)
    W = sg * Lg  # (B, nc, G, rep, Q, Q)
    xdt = xc.astype(jnp.float32) * dtc[..., None]  # (B, nc, Q, H, P)
    xdt_g = xdt.reshape(Bb, nc, Q, G, rep, P)
    y_diag = jnp.einsum("bcgrqt,bctgrp->bcqgrp", W, xdt_g)

    # ---- chunk states --------------------------------------------------------
    # decay from t to end of chunk: exp(cs[last] - cs[t])
    decay_end = jnp.exp(cs[:, :, -1:, :] - cs)  # (B, nc, Q, H)
    dg = (decay_end * dtc).reshape(Bb, nc, Q, G, rep)
    states = jnp.einsum("bctgn,bctgr,bctgrp->bcgrpn", Bc, dg, xc.reshape(Bb, nc, Q, G, rep, P).astype(jnp.float32))

    # ---- inter-chunk recurrence (sequential over chunks) ---------------------
    chunk_decay = jnp.exp(cs[:, :, -1, :])  # (B, nc, H)

    def step(h, inp):
        st, dec = inp  # st: (B, G, rep, P, N), dec: (B, H)
        h_new = h * dec.reshape(Bb, G, rep, 1, 1) + st
        return h_new, h  # emit state *before* this chunk

    h_init = (
        h0.reshape(Bb, G, rep, P, N)
        if h0 is not None
        else jnp.zeros((Bb, G, rep, P, N), jnp.float32)
    )
    h_last, h_prevs = jax.lax.scan(
        step,
        h_init,
        (states.transpose(1, 0, 2, 3, 4, 5), chunk_decay.transpose(1, 0, 2)),
    )
    h_prevs = h_prevs.transpose(1, 0, 2, 3, 4, 5)  # (B, nc, G, rep, P, N)

    # ---- inter-chunk output ---------------------------------------------------
    decay_in = jnp.exp(cs)  # decay from chunk start to q (inclusive)
    din_g = decay_in.reshape(Bb, nc, Q, G, rep)
    y_off = jnp.einsum("bcqgn,bcqgr,bcgrpn->bcqgrp", Cc, din_g, h_prevs)

    y = (y_diag + y_off).reshape(Bb, nc * Q, H, P)[:, :S]
    return y.astype(x.dtype), h_last.reshape(Bb, H, P, N)


def ssm_block(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    """Mamba-2 block forward (training/prefill)."""
    s = cfg.ssm
    cdt = x.dtype
    d_in = s.expand * cfg.d_model
    H = d_in // s.head_dim

    z = dense(x, p["w_z"].astype(cdt), pairing=p.get("w_z_pairing"))
    xi = dense(x, p["w_x"].astype(cdt), pairing=p.get("w_x_pairing"))
    Bi = dense(x, p["w_B"].astype(cdt), pairing=p.get("w_B_pairing"))
    Ci = dense(x, p["w_C"].astype(cdt), pairing=p.get("w_C_pairing"))
    dt = dense(x, p["w_dt"].astype(cdt), pairing=p.get("w_dt_pairing"))

    xi = _causal_conv(xi, p["conv_x"].astype(cdt))
    Bi = _causal_conv(Bi, p["conv_B"].astype(cdt))
    Ci = _causal_conv(Ci, p["conv_C"].astype(cdt))

    Bb, S = x.shape[:2]
    xh = xi.reshape(Bb, S, H, s.head_dim)
    Bg = Bi.reshape(Bb, S, s.n_groups, s.d_state)
    Cg = Ci.reshape(Bb, S, s.n_groups, s.d_state)
    dtp = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])

    y, _ = ssd_scan(xh, dtp, A, Bg, Cg, chunk=s.chunk)
    y = y + xh.astype(jnp.float32) * p["D"][None, None, :, None]
    y = y.reshape(Bb, S, d_in).astype(cdt)

    # gated RMSNorm then output projection
    y = y * jax.nn.silu(z)
    yf = y.astype(jnp.float32)
    y = (yf * jax.lax.rsqrt((yf * yf).mean(-1, keepdims=True) + 1e-6) * p["norm"]).astype(cdt)
    return dense(y, p["w_out"].astype(cdt), pairing=p.get("w_out_pairing"))


def ssm_decode_block(
    cfg: ModelConfig,
    p: dict,
    x: jax.Array,  # (B, 1, d)
    cache: dict,  # {"h": (B,H,P,N) fp32, "conv_x": (B,W-1,d_in), "conv_B": .., "conv_C": ..}
    pos: jax.Array,  # (B,) — unused (state carries time), kept for interface parity
) -> tuple[jax.Array, dict]:
    s = cfg.ssm
    cdt = x.dtype
    d_in = s.expand * cfg.d_model
    H = d_in // s.head_dim
    Bb = x.shape[0]

    z = dense(x, p["w_z"].astype(cdt), pairing=p.get("w_z_pairing"))[:, 0]
    xi = dense(x, p["w_x"].astype(cdt), pairing=p.get("w_x_pairing"))[:, 0]
    Bi = dense(x, p["w_B"].astype(cdt), pairing=p.get("w_B_pairing"))[:, 0]
    Ci = dense(x, p["w_C"].astype(cdt), pairing=p.get("w_C_pairing"))[:, 0]
    dt = dense(x, p["w_dt"].astype(cdt), pairing=p.get("w_dt_pairing"))[:, 0]

    def conv_step(cache_c, new, w):
        # cache_c: (B, W-1, C); new: (B, C)
        window = jnp.concatenate([cache_c, new[:, None]], axis=1)  # (B, W, C)
        out = jax.nn.silu((window * w[None]).sum(1))
        return out, window[:, 1:]

    xi, conv_x = conv_step(cache["conv_x"], xi, p["conv_x"].astype(cdt))
    Bi, conv_B = conv_step(cache["conv_B"], Bi, p["conv_B"].astype(cdt))
    Ci, conv_C = conv_step(cache["conv_C"], Ci, p["conv_C"].astype(cdt))

    xh = xi.reshape(Bb, H, s.head_dim).astype(jnp.float32)
    Bg = Bi.reshape(Bb, s.n_groups, s.d_state).astype(jnp.float32)
    Cg = Ci.reshape(Bb, s.n_groups, s.d_state).astype(jnp.float32)
    rep = H // s.n_groups
    dtp = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])  # (B, H)
    A = -jnp.exp(p["A_log"])

    h = cache["h"]  # (B, H, P, N) fp32
    dA = jnp.exp(dtp * A)  # (B, H)
    Brep = jnp.repeat(Bg, rep, axis=1)  # (B, H, N)
    Crep = jnp.repeat(Cg, rep, axis=1)
    Bx = jnp.einsum("bhp,bhn->bhpn", xh * dtp[..., None], Brep)
    h = h * dA[..., None, None] + Bx
    y = jnp.einsum("bhpn,bhn->bhp", h, Crep)
    y = y + xh * p["D"][None, :, None]
    y = y.reshape(Bb, d_in).astype(cdt)

    y = y * jax.nn.silu(z)
    yf = y.astype(jnp.float32)
    y = (yf * jax.lax.rsqrt((yf * yf).mean(-1, keepdims=True) + 1e-6) * p["norm"]).astype(cdt)
    out = dense(y, p["w_out"].astype(cdt), pairing=p.get("w_out_pairing"))
    return out[:, None], {"h": h, "conv_x": conv_x, "conv_B": conv_B, "conv_C": conv_C}
