"""Ahead-of-time compiles of the main-path kernels for a TPU v5e.

JAX ships the TPU compiler, which compiles for a chip that is described and
not attached.  These tests lower each kernel of the serving and conv paths
with ``interpret=False`` at qwen2-1.5b widths (d_model 1536, d_ff 8960,
12 query / 2 KV heads of 128) and compile it for one v5e chip, so a block
shape off the tiling rules, a scoped-VMEM overrun or an op Mosaic refuses
fails here, not on the chip.  Nothing runs: results are the CPU tests' job.

The topology is described inside a module-scoped fixture — never at import,
in a ``skipif`` or in ``parametrize`` — so every test worker collects the
same tests and only the worker that runs this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_smoke_config
from repro.core.transform import build_conv_pairings
from repro.kernels import ops
from repro.kernels.decode_attention import decode_attention_fwd, fused_decode_attention
from repro.kernels.paired_matmul import resolve_interpret
from repro.launch.steps import abstract_cache, wire_serve_cell
from repro.models import lm as M
from repro.models.lenet import init_lenet, lenet_apply
from repro.models.param import unzip
from repro.parallel.sharding import auto_mesh

D_MODEL, D_FF, H, KH, HD = 1536, 8960, 12, 2, 128
PAIRS = 762  # structured pairs of a fresh-init 1536-row weight at r=0.05
B, S = 4, 512  # serve batch and max_seq


@pytest.fixture(scope="module")
def topo():
    """A described v5e host of four chips (2x2)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def chip(topo):
    """One described v5e chip as a sharding for ``ShapeDtypeStruct``s."""
    return SingleDeviceSharding(topo.devices[0])


def _compile(chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=chip) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile()


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def test_interpret_resolves_to_compiled_kernel_on_tpu(monkeypatch):
    assert resolve_interpret(None) is True  # this suite runs on the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_interpret(None) is False
    assert resolve_interpret(True) is True


@pytest.mark.parametrize("M", [4, 512], ids=["decode", "prefill"])
def test_structured_paired_gemm(chip, M):
    bf = jnp.bfloat16
    K, N, P = D_MODEL, D_FF, PAIRS
    c = _compile(
        chip,
        lambda x, km, wr, r: ops.paired_matmul(x, km, wr, None, r, interpret=False),
        ((M, K), bf), ((P, N), bf), ((K - 2 * P, N), bf), ((M, N), bf),
    )
    assert _has_kernel(c)


@pytest.mark.parametrize("block_n", [128, 1])
def test_blocked_paired_gemm(chip, block_n):
    """The column-blocked kernel, down to the paper's per-column blocks."""
    bf = jnp.bfloat16
    N = D_FF if block_n > 1 else 1024
    nb, P = N // block_n, 700
    R = D_MODEL - 2 * P
    c = _compile(
        chip,
        lambda x, km, wr, b: ops.paired_matmul_blocked(
            x, km, wr, b, n_cols=N, interpret=False),
        ((nb, 4, D_MODEL), bf), ((nb, P, block_n), bf), ((nb, R, block_n), bf),
        ((N,), bf),
    )
    assert _has_kernel(c)


def test_decode_attention(chip):
    bf = jnp.bfloat16
    c = _compile(
        chip,
        lambda q, k, v, p: decode_attention_fwd(q, k, v, p, k_chunk=32, interpret=False),
        ((B, 1, H, HD), bf), ((B, S, KH, HD), bf), ((B, S, KH, HD), bf),
        ((B,), jnp.int32),
    )
    assert _has_kernel(c)


def test_fused_decode_attention(chip):
    """Attention + the structured paired out-projection + residual."""
    bf, i32 = jnp.bfloat16, jnp.int32
    K = N = H * HD
    P, R = PAIRS, K - 2 * PAIRS
    c = _compile(
        chip,
        lambda q, k, v, p, i, j, r, km, wr, res: fused_decode_attention(
            q, k, v, p, i, j, r, km, wr, res, n_cols=N, k_chunk=32,
            interpret=False),
        ((B, 1, H, HD), bf), ((B, S, KH, HD), bf), ((B, S, KH, HD), bf),
        ((B,), i32), ((1, P), i32), ((1, P), i32), ((1, R), i32),
        ((1, P, N), bf), ((1, R, N), bf), ((B, N), bf),
    )
    assert _has_kernel(c)


def test_fused_decode_attention_refuses_narrow_blocks_on_tpu():
    """Out-projection blocks narrower than a lane tile would hold every
    block's weights 128/bn-fold padded in VMEM: a clear error, not Mosaic's."""
    q = jnp.zeros((1, 1, 2, 8))
    kv = jnp.zeros((1, 8, 1, 8))
    idx = jnp.zeros((16, 1), jnp.int32)
    w = jnp.zeros((16, 1, 1))
    with pytest.raises(NotImplementedError, match="pair_block_n"):
        fused_decode_attention(q, kv, kv, jnp.zeros((1,), jnp.int32), idx, idx,
                               idx, w, w, None, n_cols=16, interpret=False)


def test_lenet_paired_conv_fused_pool(chip):
    """The paper's path: LeNet-5 convs on the paired kernel, 2×2 pools fused
    into the epilogue, batch 256 at fp32."""
    params = init_lenet(jax.random.key(0))
    paired = build_conv_pairings(params, 0.0)
    shapes = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    flat, tree = jax.tree.flatten(shapes, is_leaf=lambda x: isinstance(x, tuple))

    def fwd(x, *leaves):
        p = jax.tree.unflatten(tree, leaves)
        with ops.pallas_conv("pallas_paired", paired=paired, fuse_pool=True,
                             interpret=False):
            return lenet_apply(p, x)

    c = _compile(chip, fwd, ((256, 32, 32, 1), np.float32), *flat)
    assert c.as_text().count("tpu_custom_call") >= 3


def test_mesh_paired_decode_step(topo, monkeypatch):
    """The paired serve cell on a (1, 4) data×model mesh compiles: XLA cannot
    partition a Mosaic kernel, so each runs whole inside a ``shard_map``.
    The step is traced on the TPU branch and its params are placed as shapes
    (this process has no TPU to hold them)."""
    cfg = get_smoke_config("qwen2-1.5b")
    params, _ = unzip(M.init_lm(cfg, jax.random.key(0)))
    mesh = auto_mesh((1, 4), ("data", "model"), devices=topo.devices)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_put", lambda x, s=None: jax.ShapeDtypeStruct(
        np.shape(x), x.dtype, sharding=s))
    # a rounding that pairs most smoke-size weights: the subtractor segments
    # compile on the mesh, not only the residual one
    knobs = M.PerfKnobs(q_chunk=16, k_chunk=16, remat="none", gemm="pallas_paired",
                        pair_rounding=0.25)
    cell = wire_serve_cell(cfg, params, mesh, batch_size=B, max_seq=64, knobs=knobs)
    assert cell.pair_report.total_pairs > 0
    cache = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        abstract_cache(cfg, B, 64)[0], cell.c_shard)
    batch = {"tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32),
             "pos": jax.ShapeDtypeStruct((B,), jnp.int32)}
    assert _has_kernel(cell.decode.lower(cell.params, cache, batch).compile())
