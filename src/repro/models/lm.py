"""The unified LM: decoder-only / MoE / SSM / hybrid / enc-dec / VLM.

One ``init_lm`` + four step-level entry points cover every assigned
architecture:

* ``lm_loss``       — training forward + masked cross-entropy (+ MoE aux)
* ``prefill``       — inference prefill: logits for the last position and a
                      filled cache (collected as scan outputs, so the cache
                      layout *is* the (layers, batch, seq, …) scan layout)
* ``init_cache``    — empty cache ShapeDtype/array tree with logical axes
* ``decode_step``   — one new token against the cache (per-sequence positions)

The decoder stack is a list of *segments* (maximal runs of identical layer
kinds); each segment is one ``lax.scan`` over parameters stacked along a
leading "layers" axis.  ``PerfKnobs`` carries the schedule parameters the
§Perf hillclimb tunes (attention chunk sizes, remat policy).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models.param import Param, stack_params
from repro.parallel.sharding import constrain


# ---------------------------------------------------------------------------
# knobs the perf loop tunes
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PerfKnobs:
    q_chunk: int = 1024
    k_chunk: int = 1024
    remat: str = "full"  # full | dots | none
    ssd_chunk: int = 0  # 0 → config default
    xent_chunk: int = 512  # seq-chunked cross-entropy (0 → unchunked)
    precast: bool = False  # cast stacked matrices to bf16 before the scan —
    # measured NEGATIVE on mistral/train_4k (flops 2.2e15→4.4e15, §Perf it. 2)
    attn_fused: bool = False  # account flash-attention interiors as
    # VMEM-resident (the validated Pallas kernel replaces them on TPU);
    # launch/dryrun then adds the kernel's boundary HBM traffic analytically
    gemm: str = "xla"  # "xla" | "pallas" | "pallas_paired" — route layer
    # GEMMs (layers.dense) through the K-tiled epilogue-fused Pallas kernel
    # instead of XLA einsums; "pallas_paired" additionally routes every
    # weight carrying pair_params metadata (attention/MLA projections, MLP
    # and MoE-expert up/gate/down, SSM projections) through the *subtractor*
    # kernel, with the sublayer residual adds fused into the kernel epilogue
    pair_rounding: float = 0.0  # rounding size for the LM pairing artifacts
    # (gemm="pallas_paired"): ServeEngine builds pair_lm_params(params,
    # pair_rounding, mode from pair_block_n) when the params don't already
    # carry metadata.  0.0 pairs nothing but still exercises the full
    # permuted-gather + kernel path (the r=0 parity anchor)
    conv: str = "xla"  # "xla" | "im2col" | "pallas_paired" — conv lowering
    # (models.lenet consults the policy; LM archs have no 2-D convs, no-op)
    fuse_pool: bool = False  # conv→pool megakernel: absorb the 2×2 max-pool
    # into the paired-conv epilogue (pallas_paired only; one HBM writeback
    # per conv layer, no standalone pooling op in the schedule)
    attn: str = "xla"  # "xla" | "pallas_fused" — decode-attention lowering:
    # "pallas_fused" routes attention_decode_block through the fused Pallas
    # kernel (kernels.decode_attention): the single-token online softmax
    # runs in VMEM scratch and the paired out-projection + sublayer residual
    # execute in the kernel flush, so the attended values never round-trip
    # HBM; with pair_block_n >= 1 the q|k|v projections additionally
    # concatenate into one subtractor launch.  Prefill is unaffected.
    pair_block_n: int = 0  # pairing-mode spectrum for the subtractor paths:
    # 0 → structured (one shared-row pairing across all output channels);
    # n >= 1 → column-blocked (one pairing per n output channels, executed
    # by the blocked kernel; 1 == the paper's per-column pairing).  Smaller
    # blocks pair more lanes at equal rounding, at n_blocks× activation
    # bandwidth — see core.pairing.pair_rows_blocked.
    block_m: int = 0  # Pallas GEMM tile sizes; 0 → kernels.tuning heuristic
    block_n: int = 0
    block_k: int = 0
    tile_cache: str = ""  # path to a persisted kernels.tuning.TileCache;
    # measured winners there beat the VMEM heuristic ("" → heuristic only)


DEFAULT_KNOBS = PerfKnobs()


def _remat(fn, knobs: PerfKnobs):
    if knobs.remat == "none":
        return fn
    if knobs.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    return jax.checkpoint(fn)


def padded_vocab(cfg: ModelConfig) -> int:
    """Vocab rounded up to 128 so the vocab axis shards on any mesh we use."""
    return ((cfg.vocab + 127) // 128) * 128


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _init_layer(cfg: ModelConfig, kind: str, key) -> dict:
    ks = iter(jax.random.split(key, 8))
    p: dict[str, Any] = {"ln1": L.init_norm(cfg)}
    has_attn = kind in ("dense", "moe", "hybrid_full", "hybrid_swa", "encdec")
    if has_attn:
        p["attn"] = L.init_mla(cfg, next(ks)) if cfg.mla else L.init_attention(cfg, next(ks))
    if kind == "encdec":
        p["lnx"] = L.init_norm(cfg)
        p["xattn"] = L.init_attention(
            dataclasses.replace(cfg, qk_norm=False, qkv_bias=False), next(ks)
        )
    if kind in ("ssm", "hybrid_full", "hybrid_swa"):
        p["mamba"] = L.init_ssm(cfg, next(ks))
    if kind in ("hybrid_full", "hybrid_swa"):
        p["ln_attn_out"] = L.init_norm(cfg)
        p["ln_ssm_out"] = L.init_norm(cfg)
    # FFN
    if kind == "moe":
        p["ln2"] = L.init_norm(cfg)
        p["moe"] = L.init_moe(cfg, next(ks))
    elif kind == "dense" and cfg.moe is not None:
        p["ln2"] = L.init_norm(cfg)
        p["mlp"] = L.init_mlp(cfg, next(ks), d_ff=cfg.moe.d_ff_dense)
    elif kind != "ssm" and cfg.d_ff:
        p["ln2"] = L.init_norm(cfg)
        p["mlp"] = L.init_mlp(cfg, next(ks))
    return p


def init_lm(cfg: ModelConfig, key) -> dict:
    """Returns a Param tree (use param.unzip to split values/axes)."""
    Vp = padded_vocab(cfg)
    d = cfg.d_model
    n_keys = cfg.n_layers + (cfg.encoder.n_layers if cfg.encoder else 0) + 16
    keys = iter(jax.random.split(key, n_keys))

    tree: dict[str, Any] = {
        "embed": L._dense_init(next(keys), (Vp, d), ("vocab", "embed"), scale_dim=1),
        "final_norm": L.init_norm(cfg),
    }
    if not cfg.tie_embeddings:
        tree["lm_head"] = L._dense_init(next(keys), (d, Vp), ("embed", "vocab"))
    if cfg.meta_tokens:
        tree["meta"] = Param(
            jax.random.normal(next(keys), (cfg.meta_tokens, d)) * 0.02, ("meta", "embed")
        )
    if cfg.vision_prefix:
        tree["vision_proj"] = L._dense_init(
            next(keys), (cfg.vision_embed_dim, d), ("head_dim", "embed")
        )

    segs = []
    for kind, count in cfg.segments():
        seg_kind = "encdec" if cfg.family == "encdec" else kind
        stacked = stack_params(
            [_init_layer(cfg, seg_kind, next(keys)) for _ in range(count)]
        )
        segs.append({"kind": seg_kind, "params": stacked})
    tree["segments"] = [s["params"] for s in segs]

    if cfg.encoder is not None:
        enc_layers = [
            {
                "ln1": L.init_norm(cfg),
                "attn": L.init_attention(
                    dataclasses.replace(cfg, qkv_bias=False, qk_norm=False), next(keys)
                ),
                "ln2": L.init_norm(cfg),
                "mlp": L.init_mlp(cfg, next(keys)),
            }
            for _ in range(cfg.encoder.n_layers)
        ]
        tree["encoder"] = {
            "segments": [stack_params(enc_layers)],
            "final_norm": L.init_norm(cfg),
        }
    return tree


def segment_kinds(cfg: ModelConfig) -> list[tuple[str, int]]:
    if cfg.family == "encdec":
        return [("encdec", n) for _, n in cfg.segments()]
    return list(cfg.segments())


# ---------------------------------------------------------------------------
# embedding / head
# ---------------------------------------------------------------------------


def _sinusoid(positions: jax.Array, d: int) -> jax.Array:
    """(B, S) → (B, S, d) sinusoidal embedding (whisper-style stub)."""
    half = d // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / max(half - 1, 1))
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


@jax.named_scope("embed")
def embed_tokens(cfg: ModelConfig, params: dict, tokens: jax.Array, cdt) -> jax.Array:
    h = jnp.take(params["embed"], tokens, axis=0).astype(cdt)
    return h * jnp.asarray(math.sqrt(cfg.d_model), cdt) if cfg.tie_embeddings else h


@jax.named_scope("head")
def lm_logits(cfg: ModelConfig, params: dict, h: jax.Array) -> jax.Array:
    h = L.apply_norm(params["final_norm"], h)
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", h, params["embed"].astype(h.dtype))
    else:
        logits = jnp.einsum("bsd,dv->bsv", h, params["lm_head"].astype(h.dtype))
    Vp = logits.shape[-1]
    pad_mask = jnp.arange(Vp) >= cfg.vocab
    logits = jnp.where(pad_mask[None, None, :], -1e9, logits.astype(jnp.float32))
    return constrain(logits, "batch", None, "vocab")


# ---------------------------------------------------------------------------
# decoder layer forward (training / prefill)
# ---------------------------------------------------------------------------


def _mixer_scope(kind: str) -> str:
    """Named scope of a layer's token mixer in a profiler trace: ``attn``
    (with any cross attention or SSM beside it), or ``ssm`` alone."""
    return "ssm" if kind == "ssm" else "attn"


@jax.named_scope("mlp")
def _ffn(cfg: ModelConfig, p: dict, h: jax.Array):
    """The feed-forward sublayer (gated MLP or MoE) with its skip connection
    (fused into the down-projection under the paired policy); returns
    ``(h, aux)``, the MoE balance loss or 0."""
    x2 = L.apply_norm(p["ln2"], h)
    if "moe" in p:
        y2, aux = L.moe_block(cfg, p["moe"], x2)
        return h + y2, aux
    return L.mlp_block(cfg, p["mlp"], x2, residual=h), jnp.float32(0.0)


def _window_for(cfg: ModelConfig, kind: str) -> int:
    if kind == "hybrid_swa":
        return cfg.sliding_window
    if kind in ("dense", "moe") and cfg.sliding_window:
        return cfg.sliding_window
    return 0


def layer_fwd(
    cfg: ModelConfig,
    kind: str,
    p: dict,
    h: jax.Array,
    positions: jax.Array,
    enc_out: jax.Array | None,
    knobs: PerfKnobs,
    collect_cache: bool = False,
):
    """Returns (h, aux, cache_entry or None)."""
    aux = jnp.float32(0.0)
    cache = None
    window = _window_for(cfg, kind)
    n_sink = cfg.meta_tokens

    with jax.named_scope(_mixer_scope(kind)):
        x = L.apply_norm(p["ln1"], h)
        if kind == "ssm":
            y, ssm_cache = _ssm_with_cache(cfg, p["mamba"], x, collect_cache)
            h = h + y
            cache = ssm_cache
        elif kind in ("hybrid_full", "hybrid_swa"):
            attn_cache = None
            if cfg.mla:
                a = L.mla_block(cfg, p["attn"], x, positions, q_chunk=knobs.q_chunk, k_chunk=knobs.k_chunk)
            else:
                a, attn_cache = _attn_with_cache(
                    cfg, p["attn"], x, positions, window, n_sink, knobs, collect_cache
                )
            m, ssm_cache = _ssm_with_cache(cfg, p["mamba"], x, collect_cache)
            y = 0.5 * (L.apply_norm(p["ln_attn_out"], a) + L.apply_norm(p["ln_ssm_out"], m))
            h = h + y
            if collect_cache:
                cache = {**(attn_cache or {}), **(ssm_cache or {})}
        else:  # dense / moe / encdec — attention first
            if cfg.mla:
                if collect_cache:
                    a, cache = _mla_with_cache(cfg, p["attn"], x, positions, knobs)
                else:
                    a = L.mla_block(cfg, p["attn"], x, positions, q_chunk=knobs.q_chunk, k_chunk=knobs.k_chunk)
                h = h + a
            else:
                # the skip connection rides the out-projection (fused into the
                # paired kernel's epilogue under gemm="pallas_paired")
                h, cache = _attn_with_cache(
                    cfg, p["attn"], x, positions, window, n_sink, knobs,
                    collect_cache, residual=h,
                )
            if kind == "encdec":
                xq = L.apply_norm(p["lnx"], h)
                # skip connection rides the paired out-projection epilogue
                h = _cross_attention(cfg, p["xattn"], xq, enc_out, knobs, residual=h)

    if "mlp" in p or "moe" in p:
        h, aux = _ffn(cfg, p, h)

    h = constrain(h, "batch", "seq", None)
    return h, aux, cache


def _attn_with_cache(cfg, p, x, positions, window, n_sink, knobs, collect_cache,
                     residual=None):
    q, k, v = L._qkv(cfg, p, x, positions)
    q = constrain(q, "batch", None, "q_heads", None)
    k = constrain(k, "batch", None, "kv_heads", None)
    v = constrain(v, "batch", None, "kv_heads", None)
    out = L.flash_attention(
        q, k, v, causal=True, window=window, n_sink=n_sink,
        q_chunk=knobs.q_chunk, k_chunk=knobs.k_chunk,
    )
    y = L.attn_out_proj(p, out, residual=residual)
    cache = None
    if collect_cache:
        k = constrain(k, "batch", "cache_seq", "kv_heads", "head_dim")
        v = constrain(v, "batch", "cache_seq", "kv_heads", "head_dim")
        cache = {"k": k, "v": v}
    return y, cache


def _mla_with_cache(cfg, p, x, positions, knobs):
    """MLA prefill that also emits the compressed (c_kv, k_rope) cache."""
    m = cfg.mla
    cdt = x.dtype
    d = x.shape[-1]
    H = cfg.n_heads
    qk = m.qk_nope_dim + m.qk_rope_dim
    c_kv = L.dense(x, p["w_dkv"].astype(cdt), pairing=p.get("w_dkv_pairing"))
    c_kv = L.rms_head_norm(p["kv_norm"], c_kv)
    k_rope = L.dense(x, p["w_kr"].astype(cdt), pairing=p.get("w_kr_pairing"))
    k_rope = L.rope(k_rope[:, :, None, :], positions, cfg.rope_theta)[:, :, 0, :]

    q = L.dense(x, p["wq"].astype(cdt).reshape(d, H * qk),
                pairing=p.get("wq_pairing")).reshape(*x.shape[:-1], H, qk)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_dim], axis=-1)
    q_rope = L.rope(q_rope, positions, cfg.rope_theta)
    k_nope = jnp.einsum("bsr,rhk->bshk", c_kv, p["w_uk"].astype(cdt))
    v = jnp.einsum("bsr,rhk->bshk", c_kv, p["w_uv"].astype(cdt))
    k_rope_b = jnp.broadcast_to(k_rope[:, :, None, :], k_rope.shape[:2] + (H, m.qk_rope_dim))
    qc = jnp.concatenate([q_nope, q_rope], axis=-1)
    kc = jnp.concatenate([k_nope, k_rope_b], axis=-1)
    out = L.flash_attention(
        qc, kc, jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, qc.shape[-1] - v.shape[-1]))),
        causal=True, q_chunk=knobs.q_chunk, k_chunk=knobs.k_chunk,
    )[..., : m.v_head_dim]
    y = L.dense(out.reshape(*out.shape[:-2], H * m.v_head_dim),
                p["wo"].astype(cdt).reshape(H * m.v_head_dim, d),
                pairing=p.get("wo_pairing"))
    c_kv_c = constrain(c_kv, "batch", "cache_seq", "kv_lora")
    k_rope_c = constrain(k_rope, "batch", "cache_seq", "head_dim")
    return y, {"c_kv": c_kv_c, "k_rope": k_rope_c}


def _xattn_q(p, xq):
    """Cross-attention query projection through `layers.dense` so the wq
    pairing metadata (configs with xattn paired_leaves) reaches the
    subtractor kernel — the k/v projections run over the *encoder* output
    once at prefill and stay plain einsums."""
    cdt = xq.dtype
    d = xq.shape[-1]
    w = p["wq"].astype(cdt)
    h, hd = w.shape[-2:]
    q = L.dense(xq, w.reshape(d, h * hd), pairing=p.get("wq_pairing"))
    return q.reshape(*xq.shape[:-1], h, hd)


def _cross_attention(cfg, p, xq, enc_out, knobs, residual=None):
    cdt = xq.dtype
    q = _xattn_q(p, xq)
    k = jnp.einsum("bsd,dhk->bshk", enc_out, p["wk"].astype(cdt))
    v = jnp.einsum("bsd,dhk->bshk", enc_out, p["wv"].astype(cdt))
    out = L.flash_attention(q, k, v, causal=False, q_chunk=knobs.q_chunk, k_chunk=knobs.k_chunk)
    return L.attn_out_proj(p, out, residual=residual)


def _ssm_with_cache(cfg, p, x, collect_cache):
    if not collect_cache:
        return L.ssm_block(cfg, p, x), None
    # prefill: run the block but also emit (h_final, conv tails)
    s = cfg.ssm
    cdt = x.dtype
    d_in = s.expand * cfg.d_model
    H = d_in // s.head_dim
    z = L.dense(x, p["w_z"].astype(cdt), pairing=p.get("w_z_pairing"))
    xi0 = L.dense(x, p["w_x"].astype(cdt), pairing=p.get("w_x_pairing"))
    Bi0 = L.dense(x, p["w_B"].astype(cdt), pairing=p.get("w_B_pairing"))
    Ci0 = L.dense(x, p["w_C"].astype(cdt), pairing=p.get("w_C_pairing"))
    dt = L.dense(x, p["w_dt"].astype(cdt), pairing=p.get("w_dt_pairing"))
    xi = L._causal_conv(xi0, p["conv_x"].astype(cdt))
    Bi = L._causal_conv(Bi0, p["conv_B"].astype(cdt))
    Ci = L._causal_conv(Ci0, p["conv_C"].astype(cdt))
    Bb, S = x.shape[:2]
    xh = xi.reshape(Bb, S, H, s.head_dim)
    Bg = Bi.reshape(Bb, S, s.n_groups, s.d_state)
    Cg = Ci.reshape(Bb, S, s.n_groups, s.d_state)
    dtp = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    y, h_last = L.ssd_scan(xh, dtp, A, Bg, Cg, chunk=s.chunk)
    y = y + xh.astype(jnp.float32) * p["D"][None, None, :, None]
    y = y.reshape(Bb, S, d_in).astype(cdt)
    y = y * jax.nn.silu(z)
    yf = y.astype(jnp.float32)
    y = (yf * jax.lax.rsqrt((yf * yf).mean(-1, keepdims=True) + 1e-6) * p["norm"]).astype(cdt)
    out = L.dense(y, p["w_out"].astype(cdt), pairing=p.get("w_out_pairing"))
    W = s.conv_width
    cache = {
        "h": h_last,
        "conv_x": xi0[:, -(W - 1):],
        "conv_B": Bi0[:, -(W - 1):],
        "conv_C": Ci0[:, -(W - 1):],
    }
    return out, cache


# ---------------------------------------------------------------------------
# encoder (whisper)
# ---------------------------------------------------------------------------


def encoder_fwd(cfg: ModelConfig, enc_params: dict, frames: jax.Array, knobs: PerfKnobs) -> jax.Array:
    """frames: (B, F, d_model) precomputed frame embeddings (conv stub)."""
    cdt = frames.dtype
    B, F, _ = frames.shape
    pos = jnp.broadcast_to(jnp.arange(F, dtype=jnp.int32), (B, F))
    h = frames + _sinusoid(pos, cfg.d_model).astype(cdt)

    def body(carry, lp):
        h = carry
        x = L.apply_norm(lp["ln1"], h)
        a = L.attention_block(
            dataclasses.replace(cfg, qkv_bias=False, qk_norm=False),
            lp["attn"], x, pos, causal=False,
            q_chunk=knobs.q_chunk, k_chunk=knobs.k_chunk,
        )
        h = h + a
        x2 = L.apply_norm(lp["ln2"], h)
        h = h + L.mlp_block(cfg, lp["mlp"], x2)
        return h, None

    for seg in enc_params["segments"]:
        h, _ = jax.lax.scan(_remat(body, knobs), h, seg)
    return L.apply_norm(enc_params["final_norm"], h)


# ---------------------------------------------------------------------------
# full forward (training / prefill)
# ---------------------------------------------------------------------------


def _prepare_inputs(cfg: ModelConfig, params: dict, batch: dict, knobs: PerfKnobs):
    """Embeds tokens (+ meta tokens / vision patches), runs encoder if any.

    Returns (h, positions, enc_out, logits_offset) where logits_offset is the
    number of prefix positions (meta tokens) to strip from outputs.
    """
    cdt = jnp.dtype(cfg.dtype)
    tokens = batch["tokens"]
    B, S = tokens.shape
    h = embed_tokens(cfg, params, tokens, cdt)

    if cfg.vision_prefix:
        patches = batch["patches"].astype(cdt)  # (B, P, vision_embed_dim)
        pe = jnp.einsum("bpe,ed->bpd", patches, params["vision_proj"].astype(cdt))
        h = jnp.concatenate([pe, h[:, cfg.vision_prefix :]], axis=1)

    offset = 0
    if cfg.meta_tokens:
        meta = jnp.broadcast_to(
            params["meta"].astype(cdt)[None], (B, cfg.meta_tokens, cfg.d_model)
        )
        h = jnp.concatenate([meta, h], axis=1)
        offset = cfg.meta_tokens

    St = h.shape[1]
    positions = jnp.broadcast_to(jnp.arange(St, dtype=jnp.int32), (B, St))
    if cfg.family == "encdec":
        h = h + _sinusoid(positions, cfg.d_model).astype(cdt)

    enc_out = None
    if cfg.encoder is not None:
        enc_out = encoder_fwd(cfg, params["encoder"], batch["frames"].astype(cdt), knobs)

    h = constrain(h, "batch", "seq", None)
    return h, positions, enc_out, offset


def _precast_segments(cfg: ModelConfig, params: dict) -> dict:
    """Cast matrix params to the compute dtype once, *before* the layer scan.

    With FSDP (fp32 master weights 2-D sharded over data×model), casting
    inside the scan means the per-layer all-gather moves fp32 — and XLA may
    hoist the gather out of the loop, materializing the full fp32 stack per
    model shard (~30 GiB for the 123B config; EXPERIMENTS.md §Perf it. 2).
    Casting the stacked tree first halves gather bytes and keeps the hoisted
    buffer bf16.  Vector params (norm scales, biases, A_log, dt_bias) stay
    fp32 — they are tiny and precision-critical.
    """
    cdt = jnp.dtype(cfg.dtype)

    def cast(a):
        if hasattr(a, "dtype") and jnp.issubdtype(a.dtype, jnp.floating) and a.ndim >= 3:
            # >=3: every stacked (layers, ...) matrix; stacked vectors are 2-D
            return a.astype(cdt)
        return a

    out = dict(params)
    out["segments"] = jax.tree.map(cast, params["segments"])
    if "encoder" in params:
        enc = dict(params["encoder"])
        enc["segments"] = jax.tree.map(cast, params["encoder"]["segments"])
        out["encoder"] = enc
    return out


def lm_forward(
    cfg: ModelConfig,
    params: dict,
    batch: dict,
    *,
    knobs: PerfKnobs = DEFAULT_KNOBS,
    collect_cache: bool = False,
):
    """Returns (logits, aux, cache_or_None)."""
    if knobs.precast:
        params = _precast_segments(cfg, params)
    h, positions, enc_out, offset = _prepare_inputs(cfg, params, batch, knobs)
    kinds = segment_kinds(cfg)
    caches = []
    aux_total = jnp.float32(0.0)

    for (kind, _), seg_params in zip(kinds, params["segments"], strict=True):

        def body(carry, lp, _kind=kind):
            h, aux = carry
            h2, aux2, cache = layer_fwd(
                cfg, _kind, lp, h, positions, enc_out, knobs,
                collect_cache=collect_cache,
            )
            return (h2, aux + aux2), cache

        with jax.named_scope("layer_scan"):
            (h, aux_total), seg_cache = jax.lax.scan(
                _remat(body, knobs), (h, aux_total), seg_params
            )
        caches.append(seg_cache)

    if offset:
        h = h[:, offset:]
    logits = lm_logits(cfg, params, h)
    cache = caches if collect_cache else None
    return logits, aux_total, cache


def _hidden_for_loss(
    cfg: ModelConfig,
    params: dict,
    batch: dict,
    knobs: PerfKnobs,
):
    """Forward up to the (final-normed) hidden states, skipping the logits."""
    if knobs.precast:
        params = _precast_segments(cfg, params)
    h, positions, enc_out, offset = _prepare_inputs(cfg, params, batch, knobs)
    aux_total = jnp.float32(0.0)
    for (kind, _), seg_params in zip(segment_kinds(cfg), params["segments"], strict=True):

        def body(carry, lp, _kind=kind):
            h, aux = carry
            h2, aux2, _ = layer_fwd(cfg, _kind, lp, h, positions, enc_out, knobs)
            return (h2, aux + aux2), None

        (h, aux_total), _ = jax.lax.scan(_remat(body, knobs), (h, aux_total), seg_params)
    if offset:
        h = h[:, offset:]
    return L.apply_norm(params["final_norm"], h), aux_total


def chunked_xent(
    cfg: ModelConfig,
    params: dict,
    h: jax.Array,  # (B, S, d) final-normed hiddens
    labels: jax.Array,  # (B, S)
    mask: jax.Array,  # (B, S) float32
    chunk: int,
) -> jax.Array:
    """Sequence-chunked softmax cross-entropy.

    The (B, S, vocab) fp32 logits tensor never exists: each chunk's logits
    are built, reduced to (logsumexp, label-logit), and freed; the chunk body
    is checkpointed so the backward pass rebuilds chunk logits instead of
    saving them.  This is what keeps the 152k-vocab configs inside HBM.
    """
    B, S, d = h.shape
    W = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    Vp = W.shape[0] if cfg.tie_embeddings else W.shape[1]
    chunk = min(chunk, S) if chunk else S
    n = -(-S // chunk)
    pad = n * chunk - S
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    hc = h.reshape(B, n, chunk, d).transpose(1, 0, 2, 3)
    lc = labels.reshape(B, n, chunk).transpose(1, 0, 2)
    mc = mask.reshape(B, n, chunk).transpose(1, 0, 2)

    vocab_ok = (jnp.arange(Vp) < cfg.vocab)

    @jax.checkpoint
    def body(acc, xs):
        hx, lx, mx = xs  # (B, chunk, d), (B, chunk), (B, chunk)
        if cfg.tie_embeddings:
            logits = jnp.einsum("bsd,vd->bsv", hx, W.astype(hx.dtype))
        else:
            logits = jnp.einsum("bsd,dv->bsv", hx, W.astype(hx.dtype))
        logits = jnp.where(vocab_ok[None, None, :], logits.astype(jnp.float32), -1e9)
        logits = constrain(logits, "batch", None, "vocab")
        lse = jax.nn.logsumexp(logits, axis=-1)  # (B, chunk)
        # masked-sum (not a dot_general) — partitions cleanly over the
        # sharded vocab axis with a single psum, no involuntary remat
        onehot = lx[..., None] == jnp.arange(Vp)[None, None, :]
        lab = jnp.where(onehot, logits, 0.0).sum(-1)
        return acc + ((lse - lab) * mx).sum(), None

    total, _ = jax.lax.scan(body, jnp.float32(0.0), (hc, lc, mc))
    return total


def lm_loss(
    cfg: ModelConfig,
    params: dict,
    batch: dict,
    *,
    knobs: PerfKnobs = DEFAULT_KNOBS,
):
    """Masked next-token cross-entropy (+ router aux). Returns (loss, metrics)."""
    labels = batch["labels"]
    mask = (labels >= 0).astype(jnp.float32)
    if cfg.vision_prefix:  # patch positions carry no token labels
        pos = jnp.arange(labels.shape[1])[None, :]
        mask = mask * (pos >= cfg.vision_prefix)
    denom = jnp.maximum(mask.sum(), 1.0)

    h, aux = _hidden_for_loss(cfg, params, batch, knobs)
    labels_safe = jnp.maximum(labels, 0)
    total = chunked_xent(cfg, params, h, labels_safe, mask, knobs.xent_chunk)
    xent = total / denom
    loss = xent + aux
    return loss, {"xent": xent, "aux": aux}


# ---------------------------------------------------------------------------
# caches + decode
# ---------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch_size: int, max_seq: int) -> dict:
    """Param tree (values + logical axes) for an empty decode cache.

    ``max_seq`` counts token positions; meta tokens extend it internally.
    """
    cdt = jnp.dtype(cfg.dtype)
    S = max_seq + cfg.meta_tokens
    segs = []
    for kind, count in segment_kinds(cfg):
        entry: dict[str, Param] = {}
        if kind in ("dense", "moe", "encdec", "hybrid_full", "hybrid_swa"):
            if cfg.mla:
                m = cfg.mla
                entry["c_kv"] = Param(
                    jnp.zeros((count, batch_size, S, m.kv_lora_rank), cdt),
                    ("layers", "batch", "cache_seq", "kv_lora"),
                )
                entry["k_rope"] = Param(
                    jnp.zeros((count, batch_size, S, m.qk_rope_dim), cdt),
                    ("layers", "batch", "cache_seq", "head_dim"),
                )
            else:
                KH, hd = cfg.n_kv_heads, cfg.head_dim
                # hybrid_swa layers get the same full-length (S) cache as
                # every other attention layer: the decode scatter writes at
                # absolute positions, so a window-sized ring buffer needs a
                # modular write index + rotated attention mask that do not
                # exist yet.  When that lands, allocate
                # min(S, window + meta_tokens + 1) rows here instead.
                Sc = S
                for name in ("k", "v"):
                    entry[name] = Param(
                        jnp.zeros((count, batch_size, Sc, KH, hd), cdt),
                        ("layers", "batch", "cache_seq", "kv_heads", "head_dim"),
                    )
        if kind in ("ssm", "hybrid_full", "hybrid_swa"):
            s = cfg.ssm
            d_in = s.expand * cfg.d_model
            H = d_in // s.head_dim
            GN = s.n_groups * s.d_state
            W = s.conv_width
            entry["h"] = Param(
                jnp.zeros((count, batch_size, H, s.head_dim, s.d_state), jnp.float32),
                ("layers", "batch", "ssm_heads", "head_dim", "ssm_state"),
            )
            entry["conv_x"] = Param(
                jnp.zeros((count, batch_size, W - 1, d_in), cdt),
                ("layers", "batch", "conv", "ssm_in"),
            )
            entry["conv_B"] = Param(
                jnp.zeros((count, batch_size, W - 1, GN), cdt),
                ("layers", "batch", "conv", "ssm_state"),
            )
            entry["conv_C"] = Param(
                jnp.zeros((count, batch_size, W - 1, GN), cdt),
                ("layers", "batch", "conv", "ssm_state"),
            )
        if kind == "encdec":
            F = cfg.encoder.frames
            KH, hd = cfg.n_kv_heads, cfg.head_dim
            for name in ("xk", "xv"):
                entry[name] = Param(
                    jnp.zeros((count, batch_size, F, KH, hd), cdt),
                    ("layers", "batch", "frames", "kv_heads", "head_dim"),
                )
        segs.append(entry)
    return {"segments": segs}


def layer_decode(
    cfg: ModelConfig,
    kind: str,
    p: dict,
    c: dict,
    h: jax.Array,  # (B, 1, d)
    pos: jax.Array,  # (B,) absolute position incl. meta offset
):
    window = _window_for(cfg, kind)
    n_sink = cfg.meta_tokens
    with jax.named_scope(_mixer_scope(kind)):
        x = L.apply_norm(p["ln1"], h)
        c_out = dict(c)
        if kind == "ssm":
            y, ssm_c = L.ssm_decode_block(cfg, p["mamba"], x, c, pos)
            h = h + y
            c_out.update(ssm_c)
        elif kind in ("hybrid_full", "hybrid_swa"):
            a, attn_c = L.attention_decode_block(
                cfg, p["attn"], x, {"k": c["k"], "v": c["v"]}, pos,
                window=window, n_sink=n_sink,
            )
            m, ssm_c = L.ssm_decode_block(
                cfg, p["mamba"], x,
                {k: c[k] for k in ("h", "conv_x", "conv_B", "conv_C")}, pos,
            )
            y = 0.5 * (L.apply_norm(p["ln_attn_out"], a) + L.apply_norm(p["ln_ssm_out"], m))
            h = h + y
            c_out.update(attn_c)
            c_out.update(ssm_c)
        else:
            if cfg.mla:
                a, mla_c = L.mla_decode_block(
                    cfg, p["attn"], x, {"c_kv": c["c_kv"], "k_rope": c["k_rope"]}, pos
                )
                c_out.update(mla_c)
                h = h + a
            else:
                # skip connection fused into the out-projection epilogue
                h, attn_c = L.attention_decode_block(
                    cfg, p["attn"], x, {"k": c["k"], "v": c["v"]}, pos,
                    window=window, n_sink=n_sink, residual=h,
                )
                c_out.update(attn_c)
            if kind == "encdec":
                xq = L.apply_norm(p["lnx"], h)
                # cross attention against the precomputed encoder K/V; the wq/wo
                # projections route through layers.dense so the xattn pairing
                # metadata reaches the subtractor kernel, with the skip
                # connection fused into the out-projection epilogue
                q = _xattn_q(p["xattn"], xq)
                out = L.decode_attention(
                    q, c["xk"], c["xv"],
                    jnp.full((h.shape[0],), c["xk"].shape[1] - 1, jnp.int32),
                )
                h = L.attn_out_proj(p["xattn"], out, residual=h)

    if "mlp" in p or "moe" in p:
        h, _ = _ffn(cfg, p, h)
    return h, c_out


def decode_step(
    cfg: ModelConfig,
    params: dict,
    cache: dict,
    tokens: jax.Array,  # (B, 1) the newest token
    pos: jax.Array,  # (B,) its position (0-based, token coordinates)
):
    """One decode step. Returns (logits (B, 1, V), new_cache)."""
    cdt = jnp.dtype(cfg.dtype)
    h = embed_tokens(cfg, params, tokens, cdt)
    pos_abs = pos + cfg.meta_tokens
    if cfg.family == "encdec":
        h = h + _sinusoid(pos_abs[:, None], cfg.d_model).astype(cdt)

    new_segs = []
    for (kind, _), seg_params, seg_cache in zip(
        segment_kinds(cfg), params["segments"], cache["segments"], strict=True
    ):

        def body(h, xs, _kind=kind):
            lp, c = xs
            h2, c2 = layer_decode(cfg, _kind, lp, c, h, pos_abs)
            return h2, c2

        # the scan's own ops (each layer's weights and cache sliced out of
        # the stacks, the new cache stacked back) carry only this scope
        with jax.named_scope("layer_scan"):
            h, seg_cache_new = jax.lax.scan(body, h, (seg_params, seg_cache))
        new_segs.append(seg_cache_new)

    logits = lm_logits(cfg, params, h)
    return logits, {"segments": new_segs}


# ---------------------------------------------------------------------------
# prefill (returns a serving-ready cache)
# ---------------------------------------------------------------------------


def prefill(
    cfg: ModelConfig,
    params: dict,
    batch: dict,
    *,
    knobs: PerfKnobs = DEFAULT_KNOBS,
):
    """Forward over the prompt; returns (last-position logits, cache).

    The cache tensors come straight out of the scan (layers-leading layout);
    SSM entries carry the final state, attention entries the full K/V.
    """
    logits, _, caches = lm_forward(cfg, params, batch, knobs=knobs, collect_cache=True)
    cache = {"segments": caches}
    if cfg.encoder is not None:
        # precompute cross K/V once per request
        cdt = jnp.dtype(cfg.dtype)
        enc_out = encoder_fwd(cfg, params["encoder"], batch["frames"].astype(cdt), knobs)
        for (kind, _), seg_params, entry in zip(
            segment_kinds(cfg), params["segments"], cache["segments"], strict=True
        ):
            if kind != "encdec":
                continue

            def xkv(lp):
                k = jnp.einsum("bsd,dhk->bshk", enc_out, lp["xattn"]["wk"].astype(cdt))
                v = jnp.einsum("bsd,dhk->bshk", enc_out, lp["xattn"]["wv"].astype(cdt))
                return k, v

            xk, xv = jax.vmap(xkv)(seg_params)  # over layers axis
            entry["xk"] = xk
            entry["xv"] = xv
    return logits[:, -1:], cache
