"""Seeded weights of a dense GQA decoder, made by the benchmark.

Both the system under test and the plain reference take their weights from
here, so neither takes anything the other made.  Every leaf is drawn from
the run's seed and its own path, so the reference can draw one leaf again
without the rest of the tree.  All leaves are made on the device in one
jitted call.

Leaf values, in the dtype the server holds them (floating leaves of two or
more dimensions in ``dtype``, the rest in float32):

* weight matrices: truncated normal on [-2, 2] over sqrt(fan-in);
* the embedding table: truncated normal times ``EMBED_SCALE / d_model``.
  The served equations scale tied input embeddings by sqrt(d_model), and at
  1/sqrt(d_model) the input token's own row then dominates the logits (the
  model copies its last token, by some ten logit standard deviations at
  qwen2's width), so greedy tokens would not depend on the layers a check
  should cover.  At 4 / d_model the copy term is about one standard
  deviation of the logits at widths of 1536 to 2048 and 28 to 40 layers;
* norm scales (``scale``): 1 + 0.1 N(0, 1), so a dropped norm scale shows;
* attention biases (``bq``/``bk``/``bv``): 0.2 N(0, 1), so a dropped QKV
  bias shows.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

BIAS_NAMES = ("bq", "bk", "bv")
EMBED_SCALE = 4.0


def seed_key(seed: int) -> jax.Array:
    """A key from a seed of any size (``jax.random.key`` keeps 32 bits)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    hi = seed >> 32
    while hi:
        key = jax.random.fold_in(key, hi & 0xFFFFFFFF)
        hi >>= 32
    return jax.random.fold_in(key, 0x5EED)


def path_str(path) -> str:
    """``segments/0/attn/wq`` from a ``tree_flatten_with_path`` key path."""
    parts = []
    for k in path:
        parts.append(str(getattr(k, "key", getattr(k, "idx", k))))
    return "/".join(parts)


def fan_in(path: str, shape: tuple[int, ...]) -> int:
    """Contraction length of a weight matrix, stacked over layers or not."""
    name = path.rsplit("/", 1)[-1]
    dims = shape[1:] if path.startswith("segments/") else shape
    if name == "wo":  # (heads, head_dim, d_model): contracts over heads x head_dim
        return int(np.prod(dims[:-1]))
    return int(dims[0])


def leaf(key: jax.Array, path: str, shape: tuple[int, ...], dtype) -> jax.Array:
    """The value of one leaf; traceable, so many leaves fit one jitted call."""
    k = jax.random.fold_in(key, zlib.crc32(path.encode()))
    name = path.rsplit("/", 1)[-1]
    if name == "scale":
        v = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
    elif name in BIAS_NAMES:
        v = 0.2 * jax.random.normal(k, shape, jnp.float32)
    elif name == "embed":
        v = jax.random.truncated_normal(k, -2.0, 2.0, shape, jnp.float32)
        v = v * (EMBED_SCALE / shape[1])
    else:
        v = jax.random.truncated_normal(k, -2.0, 2.0, shape, jnp.float32)
        v = v / math.sqrt(fan_in(path, shape))
    return v.astype(dtype) if len(shape) >= 2 else v


def make(seed: int, layout: dict[str, tuple[int, ...]], dtype) -> dict[str, jax.Array]:
    """Every leaf of ``layout`` (path -> shape), in one jitted call."""
    paths = sorted(layout)
    dt = jnp.dtype(dtype)

    @jax.jit
    def build(key):
        return {p: leaf(key, p, layout[p], dt) for p in paths}

    return build(seed_key(seed))


def make_tree(seed: int, shape_tree, dtype):
    """Values for a tree of shapes (``jax.eval_shape`` output), same treedef."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shape_tree)
    layout = {path_str(p): tuple(s.shape) for p, s in flat}
    values = make(seed, layout, dtype)
    return jax.tree_util.tree_unflatten(treedef, [values[path_str(p)] for p, _ in flat])
