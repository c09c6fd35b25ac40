"""Analysis targets: build the :class:`RuleContext` each CLI target exposes.

Each target traces (and where useful, compiles) one real inference path with
the same policy plumbing the benches and the serving engine use, then
declares what the schedule *must* look like — the expectations the error
rules gate on.  Weights are fresh inits: every shipped rule checks structure
(schedule, dtypes, block specs, index validity), none of which depends on
trained values, so the CLI stays fast enough for a CI job.
"""
from __future__ import annotations

import dataclasses as dc

from repro.analysis.core import RuleContext

TARGETS = ("lenet_fused", "lm_decode", "serve_step", "serve_frontend",
           "fused_attn_decode", "model_zoo", "sharded_decode")

# paired decode routes exactly the LM_PAIRED_WEIGHTS GEMMs (attention
# q/k/v/out + MLP gate/up/down) through the subtractor kernel — one HBM
# writeback each per layer
_DECODE_WRITEBACKS_PER_LAYER = 7

# with attn="pallas_fused" the three QKV projections concatenate into one
# subtractor launch and the attention + out-projection + residual fuse into
# the decode-attention kernel: qkv + attn·out + MLP gate/up/down — the
# attended values never reach HBM between attention and the out-projection
_FUSED_DECODE_WRITEBACKS_PER_LAYER = 5


def _paired_knobs():
    from repro.models import lm as M

    return M.PerfKnobs(
        q_chunk=16, k_chunk=16, remat="none",
        gemm="pallas_paired", pair_block_n=1, pair_rounding=0.05,
    )


def _smoke_lm_cfg():
    from repro.configs import get_smoke_config

    # fp32 keeps the target aligned with the parity benches (the bf16
    # subtractor dtype rule is exercised by the test suite's bf16 kernels)
    return dc.replace(get_smoke_config("qwen2-1.5b"), dtype="float32")


def _paired_lm_pieces():
    """(cfg, paired params, cache, tokens, pos, knobs) shared by the two LM
    targets."""
    import jax
    import jax.numpy as jnp

    from repro.core.transform import pair_lm_params
    from repro.models import lm as M
    from repro.models.param import unzip

    cfg = _smoke_lm_cfg()
    params, _ = unzip(M.init_lm(cfg, jax.random.key(0)))
    pm, _ = pair_lm_params(params, 0.05, mode="per_column")
    cache, _ = unzip(M.init_cache(cfg, 2, 32))
    tok = jnp.zeros((2, 1), jnp.int32)
    pos = jnp.asarray([5, 11], jnp.int32)
    return cfg, pm, cache, tok, pos, _paired_knobs()


def build_lenet_fused() -> RuleContext:
    """Fused conv→pool LeNet forward on the paired Pallas path."""
    import jax
    import jax.numpy as jnp

    from repro.core.transform import build_conv_pairings
    from repro.models.lenet import LENET_CONV_POSITIONS, init_lenet, lenet_apply

    params = init_lenet(jax.random.key(0))
    arts = build_conv_pairings(params, 0.0, positions=LENET_CONV_POSITIONS)
    x = jnp.zeros((4, 32, 32, 1), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda p, xb: lenet_apply(
            p, xb, conv_impl="pallas_paired", paired=arts, fuse_pool=True
        )
    )(params, x)
    return RuleContext(
        target="lenet_fused",
        jaxpr=jaxpr,
        pairing_artifacts=arts,
        expect={
            "fused_pool": True,
            # one megakernel writeback per conv layer, nothing else
            "pallas_calls": len(arts),
        },
    )


def build_lm_decode() -> RuleContext:
    """Single-host paired LM decode step (the ServeEngine path)."""
    import jax

    from repro.kernels.ops import perf_context
    from repro.models import lm as M

    cfg, pm, cache, tok, pos, knobs = _paired_lm_pieces()

    def step(p, c, t, s):
        with perf_context(knobs):
            return M.decode_step(cfg, p, c, t, s)

    with perf_context(knobs):
        jaxpr = jax.make_jaxpr(
            lambda p, c, t, s: M.decode_step(cfg, p, c, t, s)
        )(pm, cache, tok, pos)
    hlo = jax.jit(step).lower(pm, cache, tok, pos).compile().as_text()
    return RuleContext(
        target="lm_decode",
        jaxpr=jaxpr,
        hlo_text=hlo,
        params=pm,
        hidden_shape=(2, 1, cfg.d_model),
        expect={
            "residual_adds": 0,
            "writebacks_per_layer": _DECODE_WRITEBACKS_PER_LAYER,
            "pallas_calls": _DECODE_WRITEBACKS_PER_LAYER,  # all inside the scan
        },
    )


def build_fused_attn_decode() -> RuleContext:
    """The fused-attention paired decode step (``attn="pallas_fused"``):
    the decode-attention kernel consumes the KV cache and applies the
    paired out-projection + sublayer residual in its flush, and the q|k|v
    projections run as one concatenated subtractor launch — five HBM
    writebacks per scanned layer instead of the unfused seven, with the
    attended values never materialized in HBM."""
    import jax

    from repro.kernels.ops import perf_context
    from repro.models import lm as M

    cfg, pm, cache, tok, pos, knobs = _paired_lm_pieces()
    knobs = dc.replace(knobs, attn="pallas_fused")

    def step(p, c, t, s):
        with perf_context(knobs):
            return M.decode_step(cfg, p, c, t, s)

    with perf_context(knobs):
        jaxpr = jax.make_jaxpr(
            lambda p, c, t, s: M.decode_step(cfg, p, c, t, s)
        )(pm, cache, tok, pos)
    hlo = jax.jit(step).lower(pm, cache, tok, pos).compile().as_text()
    return RuleContext(
        target="fused_attn_decode",
        jaxpr=jaxpr,
        hlo_text=hlo,
        params=pm,
        hidden_shape=(2, 1, cfg.d_model),
        expect={
            "residual_adds": 0,
            "writebacks_per_layer": _FUSED_DECODE_WRITEBACKS_PER_LAYER,
            "pallas_calls": _FUSED_DECODE_WRITEBACKS_PER_LAYER,
        },
    )


def build_serve_step() -> RuleContext:
    """The pjit'd distributed serve step (mesh + sharding rules active)."""
    import jax

    from repro.launch.steps import build_serve_step as make_step
    from repro.parallel.rules import rules_for
    from repro.parallel.sharding import auto_mesh

    cfg, pm, cache, tok, pos, knobs = _paired_lm_pieces()
    mesh = auto_mesh((1, jax.device_count()), ("data", "model"))
    rules = rules_for(cfg, "decode", mesh)
    step = make_step(cfg, mesh, rules, knobs)
    batch = {"tokens": tok, "pos": pos}
    with jax.set_mesh(mesh):
        jaxpr = jax.make_jaxpr(step)(pm, cache, batch)
        hlo = jax.jit(step).lower(pm, cache, batch).compile().as_text()
    return RuleContext(
        target="serve_step",
        jaxpr=jaxpr,
        hlo_text=hlo,
        params=pm,
        hidden_shape=(2, 1, cfg.d_model),
        expect={
            "residual_adds": 0,
            "writebacks_per_layer": _DECODE_WRITEBACKS_PER_LAYER,
            "pallas_calls": _DECODE_WRITEBACKS_PER_LAYER,
        },
    )


def build_serve_frontend() -> RuleContext:
    """The hardened front end's *degraded* path: the unpaired
    ``gemm="pallas"`` decode step the numeric watchdog retries quarantined
    requests on (serving.guards).  Exact arithmetic, no pairing metadata —
    but the fallback must still be a sane schedule: the seven per-layer
    GEMMs on the K-tiled Pallas kernel, the two sublayer residual adds
    standalone (no epilogue fusion to hide them in), no f64 leaks."""
    import jax

    from repro.kernels.ops import perf_context
    from repro.models import lm as M
    from repro.models.param import unzip

    cfg = _smoke_lm_cfg()
    params, _ = unzip(M.init_lm(cfg, jax.random.key(0)))
    cache, _ = unzip(M.init_cache(cfg, 2, 32))
    import jax.numpy as jnp

    tok = jnp.zeros((2, 1), jnp.int32)
    pos = jnp.asarray([5, 11], jnp.int32)
    knobs = M.PerfKnobs(q_chunk=16, k_chunk=16, remat="none", gemm="pallas")

    def step(p, c, t, s):
        with perf_context(knobs):
            return M.decode_step(cfg, p, c, t, s)

    with perf_context(knobs):
        jaxpr = jax.make_jaxpr(
            lambda p, c, t, s: M.decode_step(cfg, p, c, t, s)
        )(params, cache, tok, pos)
    hlo = jax.jit(step).lower(params, cache, tok, pos).compile().as_text()
    return RuleContext(
        target="serve_frontend",
        jaxpr=jaxpr,
        hlo_text=hlo,
        params=params,
        hidden_shape=(2, 1, cfg.d_model),
        expect={
            # unpaired fallback: same seven GEMM launches per layer as the
            # paired path (attn q/k/v/out + MLP gate/up/down on the dense
            # kernel), but the residual adds stay standalone — exactly 2
            "residual_adds": 2,
            "writebacks_per_layer": _DECODE_WRITEBACKS_PER_LAYER,
            "pallas_calls": _DECODE_WRITEBACKS_PER_LAYER,
        },
    )


def build_sharded_decode() -> RuleContext:
    """The mesh-sharded paired decode cell (launch.steps.wire_serve_cell):
    per-TP-shard pairing metadata placed beside its weight shards, pjit'd
    decode step.  Primary gate: ``hlo/pairing-resharding-in-loop`` must find
    zero copies/collectives of pairing metadata inside the decode while-loop
    — the metadata is loop-invariant sharded state, and any reshard there
    would serialize every decoded token behind a gather.

    Uses a (2, n/2) mesh when the process exposes ≥ 4 devices (CI's
    mesh-decode job sets ``XLA_FLAGS=--xla_force_host_platform_device_count``)
    and degrades to (1, n) otherwise — the rule is placement-structural, so
    it bites at any mesh size."""
    import jax
    import jax.numpy as jnp

    from repro.launch.steps import wire_serve_cell
    from repro.models import lm as M
    from repro.models.param import unzip
    from repro.parallel.sharding import auto_mesh

    cfg = _smoke_lm_cfg()
    params, _ = unzip(M.init_lm(cfg, jax.random.key(0)))
    n = jax.device_count()
    shape = (2, n // 2) if n >= 4 else (1, n)
    mesh = auto_mesh(shape, ("data", "model"))
    knobs = _paired_knobs()
    cell = wire_serve_cell(
        cfg, params, mesh, batch_size=2, max_seq=32, knobs=knobs
    )
    cache, _ = unzip(M.init_cache(cfg, 2, 32))
    cache = jax.tree.map(jax.device_put, cache, cell.c_shard)
    batch = {
        "tokens": jnp.zeros((2, 1), jnp.int32),
        "pos": jnp.asarray([5, 11], jnp.int32),
    }
    with jax.set_mesh(mesh):
        hlo = cell.decode.lower(cell.params, cache, batch).compile().as_text()
    return RuleContext(
        target="sharded_decode",
        hlo_text=hlo,
        params=cell.params,
        expect={},
    )


def build_model_zoo() -> RuleContext:
    """Pairing metadata of the hardest zoo member (deepseek: MLA latents,
    leading-expert-axis MoE weights, shared experts, a leading dense layer)
    — gates the valid-permutation / padding / stacked-shape invariants on
    the expert-stacked metadata the MoE kernel path consumes."""
    import jax

    from repro.configs import get_smoke_config
    from repro.core.transform import pair_params
    from repro.models import lm as M
    from repro.models.param import unzip

    cfg = dc.replace(get_smoke_config("deepseek-v2-lite-16b"), dtype="float32")
    params, _ = unzip(M.init_lm(cfg, jax.random.key(0)))
    pm, _ = pair_params(
        params, 0.05, mode="per_column", leaves=cfg.paired_leaves or None
    )
    return RuleContext(target="model_zoo", params=pm, expect={})


_BUILDERS = {
    "lenet_fused": build_lenet_fused,
    "lm_decode": build_lm_decode,
    "serve_step": build_serve_step,
    "serve_frontend": build_serve_frontend,
    "fused_attn_decode": build_fused_attn_decode,
    "model_zoo": build_model_zoo,
    "sharded_decode": build_sharded_decode,
}


def build_context(target: str) -> RuleContext:
    if target not in _BUILDERS:
        raise ValueError(f"unknown target {target!r}; choose from {TARGETS}")
    return _BUILDERS[target]()
