"""Logical-axis sharding: the single place where "what shards where" lives.

Models annotate every parameter with *logical* axes ("embed", "ff",
"experts", …) and call :func:`constrain` on key activations with logical
names ("batch", "ff", "experts").  A :class:`Rules` table maps logical axes
to mesh axes per (architecture × mode); :func:`activate` installs
(mesh, rules) for a region of code, and everything else — NamedShardings for
pjit, with_sharding_constraint on activations — derives from that.

Why logical indirection (and not hard-coded PartitionSpecs): elasticity.
When the fleet loses a pod or the mesh is re-shaped, the launcher re-activates
the same rules on the new mesh and every sharding follows; nothing in the
model knows mesh sizes.  Rules also guard divisibility: a logical axis whose
dimension does not divide its mesh axes falls back to replication instead of
producing an invalid sharding (e.g. qwen2's 12 query heads on a 16-way model
axis).

One mesh axis is never used twice in a spec: axes are resolved in priority
order and later claims on an already-used mesh axis degrade to None.
"""
from __future__ import annotations

import contextlib
import dataclasses
import logging
import threading
from collections.abc import Callable, Mapping, Sequence
from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def auto_mesh(axis_shapes, axis_names, devices=None) -> Mesh:
    """``jax.make_mesh`` with *Auto* axes (JAX's default is Explicit): every
    sharding here comes from the logical rules — jit in/out shardings and
    :func:`constrain` — not from sharding-in-types."""
    names = tuple(axis_names)
    return jax.make_mesh(
        tuple(axis_shapes), names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(names), devices=devices,
    )


# Resolution priority: earlier names win a contested mesh axis.
PRIORITY = [
    "experts",
    "vocab",
    "ff",
    "expert_ff",
    "q_heads",
    "kv_heads",
    "ssm_heads",
    "ssm_in",
    "cache_seq",
    "batch",
    "embed",
    "kv_lora",
    "ssm_state",
    "head_dim",
    "frames",
    "meta",
    "conv",
    "layers",
    "seq",
    "pairing_meta",
]


@dataclasses.dataclass(frozen=True)
class Rules:
    """Mapping logical axis -> mesh axis (str), tuple of mesh axes, or None."""

    table: Mapping[str, Any]

    def mesh_axes(self, name: str | None):
        if name is None:
            return None
        return self.table.get(name)


_state = threading.local()


def current() -> tuple[Mesh | None, Rules | None]:
    return getattr(_state, "mesh", None), getattr(_state, "rules", None)


def current_mesh() -> Mesh | None:
    return getattr(_state, "mesh", None)


@contextlib.contextmanager
def activate(mesh: Mesh, rules: Rules):
    """Install (mesh, rules) for constrain()/spec_for_axes() in this thread."""
    prev = current()
    _state.mesh, _state.rules = mesh, rules
    try:
        yield
    finally:
        _state.mesh, _state.rules = prev


def _axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        return mesh.shape[axes]
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    return size


_log = logging.getLogger(__name__)
_fallback_state = threading.local()
_logged_fallbacks: set[tuple[str, str]] = set()


@contextlib.contextmanager
def record_spec_fallbacks():
    """Collect every replication fallback :func:`spec_for_axes` takes inside
    the with-body.

    Yields an insertion-ordered ``dict[(logical_axis, reason), count]`` —
    read it after the block.  Nested recorders shadow outer ones (each dryrun
    cell gets its own ledger)."""
    prev = getattr(_fallback_state, "sink", None)
    sink: dict[tuple[str, str], int] = {}
    _fallback_state.sink = sink
    try:
        yield sink
    finally:
        _fallback_state.sink = prev


def _note_fallback(
    explain: Callable[[str, str], None] | None, axis: str, reason: str
) -> None:
    """Route a replication fallback to the explain hook, the active
    :func:`record_spec_fallbacks` sink, and (once per distinct pair) the log,
    so silent degradation is auditable without spamming per-leaf calls."""
    if explain is not None:
        explain(axis, reason)
    sink = getattr(_fallback_state, "sink", None)
    if sink is not None:
        sink[(axis, reason)] = sink.get((axis, reason), 0) + 1
    if (axis, reason) not in _logged_fallbacks:
        _logged_fallbacks.add((axis, reason))
        _log.info("sharding fallback: axis %r replicated — %s", axis, reason)


def spec_for_axes(
    axes: Sequence[str | None],
    *,
    mesh: Mesh | None = None,
    rules: Rules | None = None,
    dim_sizes: Sequence[int] | None = None,
    explain: Callable[[str, str], None] | None = None,
) -> P:
    """PartitionSpec for a tuple of logical axis names.

    Guards: (a) each mesh axis used at most once (priority order),
    (b) divisibility — if ``dim_sizes`` given, a dim that does not divide its
    mesh axes is replicated instead.  Each guard that fires reports
    ``(logical_axis, reason)`` through ``explain=`` (if given), the active
    :func:`record_spec_fallbacks` context, and a once-per-distinct-pair log
    line — a dropped candidate is a deliberate replication, not a silent one.
    """
    if mesh is None or rules is None:
        m, r = current()
        mesh = mesh or m
        rules = rules or r
    if mesh is None or rules is None:
        return P(*([None] * len(axes)))

    order = sorted(
        range(len(axes)),
        key=lambda i: PRIORITY.index(axes[i]) if axes[i] in PRIORITY else len(PRIORITY),
    )
    used: set[str] = set()
    out: list[Any] = [None] * len(axes)
    for i in order:
        cand = rules.mesh_axes(axes[i])
        if cand is None:
            continue
        cand_t = (cand,) if isinstance(cand, str) else tuple(cand)
        if any(c in used for c in cand_t):
            taken = sorted(c for c in cand_t if c in used)
            _note_fallback(
                explain, axes[i],
                f"mesh axes {taken} already claimed by a higher-priority "
                "logical axis",
            )
            continue
        if dim_sizes is not None:
            size = dim_sizes[i]
            if size % _axis_size(mesh, cand_t) != 0:
                _note_fallback(
                    explain, axes[i],
                    f"dim {size} not divisible by mesh axes "
                    f"{list(cand_t)} (size {_axis_size(mesh, cand_t)})",
                )
                continue
        used.update(cand_t)
        out[i] = cand if isinstance(cand, str) else tuple(cand_t)
    return P(*out)


def constrain(x: jax.Array, *axes: str | None) -> jax.Array:
    """with_sharding_constraint by logical names; no-op outside activate()."""
    mesh, rules = current()
    if mesh is None or rules is None:
        return x
    spec = spec_for_axes(axes, mesh=mesh, rules=rules, dim_sizes=x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def shardings_for(axes_tree: Any, mesh: Mesh, rules: Rules, shapes_tree: Any = None) -> Any:
    """NamedSharding tree for a tree of logical-axes tuples (see param.unzip).

    ``shapes_tree``: matching tree of arrays/ShapeDtypeStructs for
    divisibility guards (recommended).
    """

    def one(axes, shaped=None):
        dims = tuple(shaped.shape) if shaped is not None else None
        spec = spec_for_axes(axes, mesh=mesh, rules=rules, dim_sizes=dims)
        return NamedSharding(mesh, spec)

    is_axes = lambda a: isinstance(a, tuple) and all(isinstance(x, str | None) for x in a)
    if shapes_tree is None:
        return jax.tree.map(one, axes_tree, is_leaf=is_axes)
    return jax.tree.map(one, axes_tree, shapes_tree, is_leaf=is_axes)


def _pairing_meta_spec(
    w_name: str,
    w_axes: tuple[str | None, ...],
    w_spec: P,
    w_shape: tuple[int, ...],
    meta_shape: tuple[int, ...],
    mesh: Mesh,
) -> P:
    """PartitionSpec of one pairing-metadata leaf, derived from its sibling
    weight's *resolved* spec (never from a fresh rule resolution — the weight
    may have been replicated by a guard the metadata dims wouldn't trip, and
    diverging placements would force a reshard inside the decode loop).

    Column-blocked metadata ``(L[, E], B, lanes)``: the block axis B shards
    exactly like the weight's leading output-column dim when (a) that dim is
    the only sharded column dim, (b) blocks are uniform (``N % B == 0``), and
    (c) block count divides the shard count's worth (``B % shards == 0``) so
    shard boundaries land on block boundaries.  Expert metadata copies the
    weight's expert-axis spec.  Everything else — layers, lanes, structured
    metadata — is replicated (always correct, and per-shard builds guarantee
    each device only *reads* its own rows anyway).
    """
    nd_m = len(meta_shape)
    out: list[Any] = [None] * nd_m
    if nd_m == 0:
        return P()
    expert = "experts" in w_axes and len(w_shape) == 4
    mat0 = 2 if expert else 1
    nd_w = len(w_shape)
    if expert and nd_m >= 2 and meta_shape[1] == w_shape[1]:
        out[1] = w_spec[1]
    block_dim = 2 if expert else 1
    # blocked metadata carries (block, lane) behind the stack dims;
    # structured carries a single lane dim — nothing to place there.
    if nd_m == block_dim + 2 and nd_w > mat0:
        if w_name == "wo":
            col_dims = [nd_w - 1]
        else:
            col_dims = list(range(mat0 + 1, nd_w))
        lead = w_spec[col_dims[0]]
        aligned = lead is not None and all(
            w_spec[d] is None for d in col_dims[1:]
        )
        if aligned:
            n_cols = 1
            for d in col_dims:
                n_cols *= w_shape[d]
            n_blocks = meta_shape[block_dim]
            shards = _axis_size(mesh, lead)
            if n_cols % n_blocks == 0 and n_blocks % shards == 0:
                out[block_dim] = lead
    return P(*out)


def paired_shardings_for(
    axes_tree: Any, mesh: Mesh, rules: Rules, shapes_tree: Any
) -> Any:
    """:func:`shardings_for` for a *paired* param tree.

    Weights and every other leaf resolve through the rule table exactly as
    :func:`shardings_for` does; ``"<name>_pairing"`` sibling dicts instead
    derive their placement from the sibling weight's resolved spec via
    :func:`_pairing_meta_spec`, so metadata always lands on the device that
    holds the weight shard it indexes.  ``shapes_tree`` is required — the
    alignment guards need concrete dims.
    """

    def is_axes(a):
        return isinstance(a, tuple) and all(isinstance(x, str | None) for x in a)

    def one(axes, shaped):
        dims = tuple(shaped.shape) if shaped is not None else None
        spec = spec_for_axes(axes, mesh=mesh, rules=rules, dim_sizes=dims)
        return NamedSharding(mesh, spec)

    def walk(axes, shapes):
        if isinstance(axes, dict):
            out = {}
            for k, a in axes.items():
                w = k[: -len("_pairing")] if k.endswith("_pairing") else None
                if (
                    w is not None
                    and isinstance(a, dict)
                    and w in axes
                    and is_axes(axes[w])
                ):
                    w_axes = axes[w]
                    w_shape = tuple(shapes[w].shape)
                    w_spec = spec_for_axes(
                        w_axes, mesh=mesh, rules=rules, dim_sizes=w_shape
                    )
                    out[k] = {
                        mk: NamedSharding(
                            mesh,
                            _pairing_meta_spec(
                                w, w_axes, w_spec, w_shape,
                                tuple(shapes[k][mk].shape), mesh,
                            ),
                        )
                        for mk in a
                    }
                else:
                    out[k] = walk(a, shapes[k])
            return out
        if is_axes(axes):
            return one(axes, shapes)
        if isinstance(axes, list | tuple):
            return type(axes)(
                walk(a, s) for a, s in zip(axes, shapes, strict=True)
            )
        return one(axes, shapes)

    return walk(axes_tree, shapes_tree)
