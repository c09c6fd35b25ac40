"""Model-level pairing pass: apply the paper's preprocessing to a whole model.

This is the framework integration of the paper's "weight preprocessor" block
(Fig. 5): it walks a parameter pytree, finds every eligible weight
contraction, runs the pairing, and returns

* the *paired-equivalent* parameters (``fold``ed weights — a drop-in
  replacement; the forward pass is unchanged and bit-identical to the
  subtractor dataflow), and
* a :class:`PairedModelReport` with per-leaf pair counts, the Table-I style
  op ledger, and the modeled ASIC power/area savings.

For the TPU fast path (structured pairing + Pallas kernel) use
``mode="structured"``; the report then also carries the per-leaf
:class:`StructuredPairing` objects that `kernels/ops.py` consumes.
"""
from __future__ import annotations

import dataclasses
from collections.abc import Callable
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cost_model import AsicCostModel, OpCounts
from repro.core.pairing import (
    BlockedPairing,
    ColumnPairing,
    StructuredPairing,
    fold_columns,
    pair_columns,
    pair_rows_blocked,
    pair_rows_blocked_sharded,
    pair_rows_structured,
    pair_rows_structured_sharded,
)


@dataclasses.dataclass
class LeafReport:
    path: str
    shape: tuple[int, ...]
    n_weights: int
    n_pairs: int
    pair_fraction: float  # fraction of weights absorbed into pairs (2P/K·N)
    pairing: ColumnPairing | StructuredPairing | BlockedPairing | None = None
    # shard-aware builds (pair_params(shards=…)): how the leaf's GEMM view was
    # split and the per-shard ledger — per-column-equivalent pairs owned by
    # each column shard (col_shards > 1) or each row shard (row_shards > 1),
    # summed over layers.  sum(shard_pairs) == n_pairs by construction; the
    # mesh-decode bench additionally checks each entry against a standalone
    # pairing of that shard's weight slice.
    row_shards: int = 1
    col_shards: int = 1
    shard_pairs: tuple[int, ...] | None = None


@dataclasses.dataclass
class PairedModelReport:
    rounding: float
    mode: str
    leaves: list[LeafReport]

    @property
    def total_weights(self) -> int:
        return sum(l.n_weights for l in self.leaves)

    @property
    def total_pairs(self) -> int:
        return sum(l.n_pairs for l in self.leaves)

    @property
    def pair_fraction(self) -> float:
        tw = self.total_weights
        return 2.0 * self.total_pairs / tw if tw else 0.0

    def op_counts(self) -> OpCounts:
        """Whole-model op ledger (positions=1: one application per weight,
        i.e. GEMM accounting; conv positions are handled by the LeNet bench)."""
        base = self.total_weights
        subs = self.total_pairs
        return OpCounts(mults=base - subs, adds=base - subs, subs=subs)

    def baseline_op_counts(self) -> OpCounts:
        return OpCounts(mults=self.total_weights, adds=self.total_weights, subs=0)

    def savings(self, model: AsicCostModel | None = None) -> dict[str, float]:
        m = model or AsicCostModel()
        return {
            "power_saving": m.power_saving(self.baseline_op_counts(), self.op_counts()),
            "area_saving": m.area_saving(self.baseline_op_counts(), self.op_counts()),
            "pair_fraction": self.pair_fraction,
        }


@dataclasses.dataclass
class PairedLayer:
    """Per-conv-layer deployment artifact for the Pallas paired-conv path.

    Produced offline by :func:`build_conv_pairings` (the paper's one-time
    weight preprocessing), consumed at inference by
    ``kernels.paired_conv.paired_conv`` — the pairing carries only the *index
    structure* (which patch lanes subtract); magnitudes are recomputed from
    the live weights inside the traced forward, so the artifact stays valid
    under ``jax.grad`` and after weight updates.
    """

    name: str
    kernel_shape: tuple[int, ...]  # (kh, kw, cin, cout)
    rounding: float
    pairing: StructuredPairing | BlockedPairing
    positions: int = 1  # output spatial positions per image (conv M-dim)

    @property
    def n_pairs(self) -> int:
        """Subtractions the kernel executes per output position (for a
        BlockedPairing: summed over blocks — each block subtracts its own
        x[I]−x[J] differences)."""
        return self.pairing.n_pairs

    def measured_op_counts(self) -> dict[str, int]:
        """What the paired kernel *executes* per inference image.

        Baseline MXU lanes equal the paper's multiply count for the layer
        (K·N·positions); every pair removes one contraction lane from each
        column it spans (all N for structured, its block's columns for
        column-blocked — ``weighted_pairs`` counts exactly that) and runs
        one VPU subtract per position.
        """
        kh, kw, cin, cout = self.kernel_shape
        K, N = kh * kw * cin, cout
        baseline = K * N * self.positions
        saved = self.pairing.weighted_pairs * self.positions
        return {
            "baseline_lanes": baseline,
            "paired_lanes": baseline - saved,
            "lanes_saved": saved,
            "subs_executed": self.n_pairs * self.positions,
        }


def build_conv_pairings(
    params: Any,
    rounding: float,
    *,
    positions: dict[str, int] | None = None,
    criterion: str = "rms",
    mode: str = "structured",
    block_n: int = 0,
) -> dict[str, PairedLayer]:
    """Emit a :class:`PairedLayer` artifact for every conv leaf of ``params``.

    ``params`` is a ``{layer_name: {"w": (kh, kw, cin, cout), ...}}`` tree
    (the LeNet layout); each 4-D float ``w`` is flattened to the im2col GEMM
    matrix (K, N) and paired for the Pallas kernel.  ``mode`` selects the
    pairing spectrum point: ``"structured"`` (default — one shared-row
    pairing for all N output channels), ``"column_blocked"`` (one pairing
    per ``block_n`` output channels; requires ``block_n >= 1``), or
    ``"per_column"`` (the paper's pairing — sugar for column_blocked with
    ``block_n=1``).  ``positions`` maps layer names to output spatial
    positions (e.g. ``models.lenet.LENET_CONV_POSITIONS``) so the artifacts
    can report measured per-image op counts.
    """
    if mode == "per_column":
        mode, block_n = "column_blocked", 1
    assert mode in ("structured", "column_blocked"), f"unknown mode {mode!r}"
    if mode == "column_blocked" and block_n < 1:
        raise ValueError("mode='column_blocked' needs block_n >= 1")
    arts: dict[str, PairedLayer] = {}
    for name, leaf in params.items():
        if not isinstance(leaf, dict) or "w" not in leaf:
            continue
        w = np.asarray(leaf["w"])
        if w.ndim != 4 or w.dtype.kind != "f":
            continue
        kh, kw, cin, cout = w.shape
        wm = w.reshape(kh * kw * cin, cout).astype(np.float64)
        if mode == "column_blocked":
            sp: StructuredPairing | BlockedPairing = pair_rows_blocked(
                wm, rounding, block_n, criterion=criterion
            )
        else:
            sp = pair_rows_structured(wm, rounding, criterion=criterion)
        arts[name] = PairedLayer(
            name=name,
            kernel_shape=tuple(w.shape),
            rounding=rounding,
            pairing=sp,
            positions=(positions or {}).get(name, 1),
        )
    return arts


def _path_str(path: Any) -> str:
    return jax.tree_util.keystr(path)


# ---------------------------------------------------------------------------
# LM pairing: artifacts for the decoder stack (kernel-executable, scan-ready)
# ---------------------------------------------------------------------------

# Decoder weights of the *dense GQA* families.  Keys are (sub-path, weight
# name); "wo" contracts over all-but-last axes (the attention out-projection
# einsum "bshk,hkd->bsd"), everything else over its leading axis.  Kept as a
# public name: tests and benches import it, and it seeds the model-agnostic
# superset below.
LM_PAIRED_WEIGHTS: tuple[tuple[str, str], ...] = (
    ("attn", "wq"),
    ("attn", "wk"),
    ("attn", "wv"),
    ("attn", "wo"),
    ("mlp", "w_gate"),
    ("mlp", "w_up"),
    ("mlp", "w_down"),
)

# Model-agnostic superset of pairing-eligible leaf specs across the model
# zoo: dense GQA projections, the MLA down-projections (wq/w_dkv/w_kr/wo —
# w_uk/w_uv stay absorbed in latent einsums), per-expert MoE weights (the
# leading-expert-axis batched GEMMs), shared experts (nested sub-path), the
# Mamba in/out projections, and the enc-dec cross-attention wq/wo (which
# route through ``layers.dense``; the cross wk/wv run once over the encoder
# output at prefill as plain einsums and stay unpaired).  ``pair_params``
# intersects this with what a tree actually carries unless the caller pins
# an explicit ``leaves=`` list (``ModelConfig.paired_leaves``).  Embeddings,
# norms, biases, routers, and the conv-scan kernels are deliberately absent:
# they are not plain GEMMs or never route through ``layers.dense``.
DEFAULT_PAIRED_LEAVES: tuple[tuple[str, str], ...] = LM_PAIRED_WEIGHTS + (
    ("attn", "w_dkv"),
    ("attn", "w_kr"),
    ("xattn", "wq"),
    ("xattn", "wo"),
    ("moe", "w_gate"),
    ("moe", "w_up"),
    ("moe", "w_down"),
    ("moe.shared", "w_gate"),
    ("moe.shared", "w_up"),
    ("moe.shared", "w_down"),
    ("mamba", "w_z"),
    ("mamba", "w_x"),
    ("mamba", "w_B"),
    ("mamba", "w_C"),
    ("mamba", "w_dt"),
    ("mamba", "w_out"),
)


def _resolve_sub(seg: Any, sub_path: str) -> dict | None:
    """The sub-dict at a dotted ``sub_path`` of a layer dict, or None."""
    node = seg
    for part in sub_path.split("."):
        if not isinstance(node, dict) or part not in node:
            return None
        node = node[part]
    return node if isinstance(node, dict) else None


def _set_sub(seg: dict, sub_path: str, new_sub: dict) -> None:
    """Replace the sub-dict at ``sub_path``, shallow-copying intermediates."""
    parts = sub_path.split(".")
    node = seg
    for part in parts[:-1]:
        node[part] = dict(node[part])
        node = node[part]
    node[parts[-1]] = new_sub


def _lm_weight_matrix_shape(name: str, shape: tuple[int, ...]) -> tuple[int, int]:
    """(K, N) GEMM view of one *per-layer* decoder weight shape."""
    if name == "wo":
        K = int(np.prod(shape[:-1]))
        return K, int(shape[-1])
    return int(shape[0]), int(np.prod(shape[1:]))


def _stack_structured(pairings: list[StructuredPairing]) -> dict[str, np.ndarray]:
    """Pad per-layer structured pairings to a common (Pmax, Rmax) and stack.

    Padded pair lanes point ``I == J == 0`` (their subtract is exactly
    zero) and padded residual lanes at row 0 with a zero mask, so padding
    contracts against nothing — the zero-lane trick the kernel's k-tile
    padding already relies on.
    """
    L = len(pairings)
    P = max((sp.n_pairs for sp in pairings), default=0)
    R = max((len(sp.resid) for sp in pairings), default=0)
    I_m = np.zeros((L, P), np.int32)
    J_m = np.zeros((L, P), np.int32)
    R_m = np.zeros((L, R), np.int32)
    pmask = np.zeros((L, P), np.float32)
    rmask = np.zeros((L, R), np.float32)
    for l, sp in enumerate(pairings):
        p, r = sp.n_pairs, len(sp.resid)
        I_m[l, :p] = sp.I
        J_m[l, :p] = sp.J
        R_m[l, :r] = sp.resid
        pmask[l, :p] = 1.0
        rmask[l, :r] = 1.0
    return {"I": I_m, "J": J_m, "resid": R_m,
            "pair_mask": pmask, "resid_mask": rmask}


def _stack_blocked(pairings: list[BlockedPairing]) -> dict[str, np.ndarray]:
    """Pad per-layer blocked index matrices to common (Pmax, Rmax), stack."""
    L = len(pairings)
    B = pairings[0].n_blocks
    P = max(bp.Pmax for bp in pairings)
    R = max(bp.Rmax for bp in pairings)
    I_m = np.zeros((L, B, P), np.int32)
    J_m = np.zeros((L, B, P), np.int32)
    R_m = np.zeros((L, B, R), np.int32)
    pmask = np.zeros((L, B, P), np.float32)
    rmask = np.zeros((L, B, R), np.float32)
    for l, bp in enumerate(pairings):
        idx = bp.index_arrays()
        p, r = bp.Pmax, bp.Rmax
        I_m[l, :, :p] = idx["I"]
        J_m[l, :, :p] = idx["J"]
        R_m[l, :, :r] = idx["resid"]
        pmask[l, :, :p] = idx["pair_mask"]
        rmask[l, :, :r] = idx["resid_mask"]
    return {"I": I_m, "J": J_m, "resid": R_m,
            "pair_mask": pmask, "resid_mask": rmask}


def _any_pairing(node: Any) -> bool:
    if not isinstance(node, dict):
        return False
    return any(k.endswith("_pairing") or _any_pairing(v) for k, v in node.items())


def has_lm_pairing(params: Any) -> bool:
    """True iff ``params`` already carries pair_params metadata (any depth:
    decoder segments, encoder segments, nested shared-expert blocks)."""
    if not isinstance(params, dict):
        return False
    trees = [params.get("segments", [])]
    enc = params.get("encoder")
    if isinstance(enc, dict):
        trees.append(enc.get("segments", []))
    return any(_any_pairing(seg) for segs in trees for seg in segs)


def _pair_conv_tree(
    params: Any,
    rounding: float,
    *,
    mode: str,
    block_n: int,
    criterion: str,
    min_dim: int,
) -> tuple[Any, PairedModelReport]:
    """The conv-tree arm of :func:`pair_params`: ``{name: {"w": 4-D}}``.

    Emits the same ``"w_pairing"`` metadata-sibling layout as the LM arm,
    just unstacked (no layer axis — conv trees are not scanned).  The
    executable conv path keeps consuming :func:`build_conv_pairings`
    artifacts; this arm exists so one entry point reports any tree.
    """
    leaves_report: list[LeafReport] = []
    out = dict(params)
    for name, leaf in params.items():
        if not isinstance(leaf, dict) or "w" not in leaf:
            continue
        w = np.asarray(leaf["w"])
        if w.ndim != 4 or w.dtype.kind != "f":
            continue
        kh, kw, cin, cout = w.shape
        K, N = kh * kw * cin, cout
        if K < min_dim or N < min_dim:
            continue
        wm = w.reshape(K, N).astype(np.float64)
        if mode == "column_blocked":
            bp = pair_rows_blocked(wm, rounding, block_n, criterion=criterion)
            idx = bp.index_arrays()
            meta = {
                "I": idx["I"].astype(np.int32),
                "J": idx["J"].astype(np.int32),
                "resid": idx["resid"].astype(np.int32),
                "pair_mask": idx["pair_mask"].astype(np.float32),
                "resid_mask": idx["resid_mask"].astype(np.float32),
            }
            n_pairs = bp.weighted_pairs
            pairing: StructuredPairing | BlockedPairing = bp
        else:
            sp = pair_rows_structured(wm, rounding, criterion=criterion)
            meta = {
                "I": np.asarray(sp.I, np.int32),
                "J": np.asarray(sp.J, np.int32),
                "resid": np.asarray(sp.resid, np.int32),
                "pair_mask": np.ones(sp.n_pairs, np.float32),
                "resid_mask": np.ones(len(sp.resid), np.float32),
            }
            n_pairs = sp.weighted_pairs
            pairing = sp
        new_leaf = dict(leaf)
        new_leaf["w_pairing"] = meta
        out[name] = new_leaf
        leaves_report.append(
            LeafReport(
                path=f"{name}.w",
                shape=tuple(w.shape),
                n_weights=int(w.size),
                n_pairs=int(n_pairs),
                pair_fraction=2.0 * n_pairs / w.size,
                pairing=pairing,
            )
        )
    if not leaves_report:
        raise ValueError(
            "pair_params: no pairing-eligible conv leaves — expected a "
            "{name: {'w': (kh, kw, cin, cout)}} tree with float kernels of "
            f"GEMM dims >= {min_dim}; got keys {sorted(params)!r}"
        )
    report = PairedModelReport(rounding=rounding, mode=mode, leaves=leaves_report)
    return out, report


def _structured_shard_ledger(
    pairings: list[StructuredPairing], row_shards: int
) -> tuple[int, ...]:
    """Per-row-shard weighted pair counts (both rows of a shard-constrained
    pair live in the same shard, so attribution by I is exact)."""
    out = np.zeros(row_shards, np.int64)
    for sp in pairings:
        step = sp.shape[0] // row_shards
        if len(sp.I):
            idx = np.minimum(np.asarray(sp.I, np.int64) // step, row_shards - 1)
            out += np.bincount(idx, minlength=row_shards) * sp.shape[1]
    return tuple(int(x) for x in out)


def _blocked_shard_ledger(
    pairings: list[BlockedPairing], row_shards: int, col_shards: int
) -> tuple[int, ...] | None:
    """Per-shard weighted pair counts of a blocked build, summed over layers.

    Column shards own contiguous runs of blocks (the alignment check in
    ``pair_stack`` guarantees block boundaries land on shard boundaries);
    with only row shards, pairs are attributed by which row slab they
    live in.
    """
    if col_shards > 1:
        out = np.zeros(col_shards, np.int64)
        for bp in pairings:
            per = bp.n_blocks // col_shards
            for b, sp in enumerate(bp.blocks):
                out[min(b // per, col_shards - 1)] += sp.n_pairs * sp.shape[1]
        return tuple(int(x) for x in out)
    if row_shards > 1:
        out = np.zeros(row_shards, np.int64)
        for bp in pairings:
            step = bp.shape[0] // row_shards
            for sp in bp.blocks:
                if len(sp.I):
                    idx = np.minimum(
                        np.asarray(sp.I, np.int64) // step, row_shards - 1
                    )
                    out += np.bincount(idx, minlength=row_shards) * sp.shape[1]
        return tuple(int(x) for x in out)
    return None


def tp_shard_plan(
    param_axes: Any,
    params: Any,
    mesh,
    rules,
    *,
    leaves: tuple[tuple[str, str], ...] | None = None,
) -> dict[tuple[str, str], tuple[int, int]]:
    """(row_shards, col_shards) of every paired leaf's per-layer GEMM view.

    Resolves each eligible weight's logical axes against (mesh, rules) —
    the same ``spec_for_axes`` call that will place the weight — and counts
    how many ways the GEMM's contraction rows and output columns are split.
    A split only counts when it is the *leading* dim of the flattened view
    (contiguous chunks; a sharded trailing dim like head_dim would interleave
    and cannot express a contiguous row/column split — such leaves stay at 1,
    which is always safe: unconstrained metadata is correct everywhere, it
    just loses shard locality).  Leaves that appear with conflicting splits
    (e.g. an encoder head count that doesn't divide where the decoder's does)
    degrade to (1, 1).

    Feed the result to ``pair_params(shards=…)`` so pairing never crosses a
    shard boundary of the mesh the decode will run on.
    """
    from repro.parallel.sharding import spec_for_axes

    def mesh_size(entry) -> int:
        names = (entry,) if isinstance(entry, str) else tuple(entry)
        size = 1
        for a in names:
            size *= mesh.shape[a]
        return size

    specs = tuple(leaves) if leaves is not None else DEFAULT_PAIRED_LEAVES
    plan: dict[tuple[str, str], tuple[int, int]] = {}

    def scan_segments(ax_segments: list, val_segments: list) -> None:
        for ax_seg, val_seg in zip(ax_segments, val_segments, strict=True):
            for sub_path, w_name in specs:
                ax_sub = _resolve_sub(ax_seg, sub_path)
                val_sub = _resolve_sub(val_seg, sub_path)
                if ax_sub is None or val_sub is None or w_name not in ax_sub:
                    continue
                w_axes = ax_sub[w_name]
                shape = tuple(getattr(val_sub[w_name], "shape", ()))
                if not isinstance(w_axes, tuple) or len(w_axes) != len(shape):
                    continue
                nd = len(shape)
                expert = sub_path.split(".")[-1] == "moe" and nd == 4
                mat0 = 2 if expert else 1
                if nd <= mat0:
                    continue
                spec = spec_for_axes(
                    w_axes, mesh=mesh, rules=rules, dim_sizes=shape
                )
                if w_name == "wo":
                    row_dims = list(range(mat0, nd - 1))
                    col_dims = [nd - 1]
                else:
                    row_dims = [mat0]
                    col_dims = list(range(mat0 + 1, nd))

                def split(dims, spec=spec):
                    lead = spec[dims[0]]
                    if lead is None or any(spec[d] is not None for d in dims[1:]):
                        return 1
                    return mesh_size(lead)

                rc = (split(row_dims), split(col_dims))
                key = (sub_path, w_name)
                if key in plan and plan[key] != rc:
                    plan[key] = (1, 1)
                else:
                    plan[key] = rc

    scan_segments(
        param_axes.get("segments", []), params.get("segments", [])
    )
    ax_enc, val_enc = param_axes.get("encoder"), params.get("encoder")
    if isinstance(ax_enc, dict) and isinstance(val_enc, dict):
        scan_segments(ax_enc.get("segments", []), val_enc.get("segments", []))
    return plan


def pair_params(
    params: Any,
    rounding: float,
    *,
    mode: str = "structured",
    block_n: int = 0,
    leaves: tuple[tuple[str, str], ...] | None = None,
    criterion: str = "rms",
    min_dim: int = 8,
    shards: Any = None,
) -> tuple[Any, PairedModelReport]:
    """Pairing artifacts for every eligible weight of *any* param tree.

    One model-agnostic entry point covering the whole zoo:

    * **conv trees** (``{name: {"w": 4-D}}``, no ``"segments"`` key) — each
      kernel paired as its im2col GEMM matrix, unstacked metadata;
    * **stacked decoder/encoder weights** (``params["segments"]`` and
      ``params["encoder"]["segments"]``, the lax.scan layout) — per-layer
      pairings padded to the segment-wide (Pmax, Rmax) and stacked on the
      layer axis, which a scan slices exactly like the weights themselves;
    * **leading-expert-axis batched weights** (MoE ``(L, E, K, F)`` leaves)
      — paired per layer *per expert*, metadata stacked ``(L, E, …)`` so the
      expert axis rides next to the layer axis and the blocked kernel can
      treat experts as column blocks.

    Leaf selection is by ``(sub-path, weight-name)`` specs — dotted
    sub-paths address nested blocks (``"moe.shared"``).  With ``leaves=None``
    the :data:`DEFAULT_PAIRED_LEAVES` superset is intersected with what the
    tree carries; passing an explicit list (``ModelConfig.paired_leaves``)
    additionally *requires* every spec to match at least one segment, so a
    renamed or mistyped weight fails loudly instead of silently falling off
    the paired path.  Either way a tree yielding *no* pairing metadata at
    all raises, listing what was looked for and what the tree carries.

    Returns ``(params', report)``: the same tree with a sibling
    ``"<name>_pairing"`` metadata entry next to each paired weight.  Weights
    are **not** folded — magnitudes are recomputed live inside the trace
    (``kernels.ops.fused_paired_dense`` / ``fused_paired_expert_dense``), so
    the artifact survives ``jax.grad`` and weight updates.

    ``mode`` picks the pairing-spectrum point: ``"structured"`` (one
    shared-row pairing per matrix), ``"column_blocked"`` (one per
    ``block_n`` output columns), or ``"per_column"`` (sugar for
    ``block_n=1`` — the paper's Algorithm 1).

    ``shards`` (optional) makes the build *shard-aware*: a mapping from
    ``(sub_path, weight_name)`` to ``(row_shards, col_shards)`` of the
    leaf's per-layer GEMM view (:func:`tp_shard_plan` derives one from a
    mesh + rule table).  Row shards constrain the pairing so no pair spans a
    contraction-shard boundary (each tensor-parallel device's metadata is
    exactly what it would build from its local rows); column shards are
    checked for block alignment (a shard boundary must not split a pairing
    block — misaligned leaves degrade to an unsharded build) and drive the
    per-shard ledger in each :class:`LeafReport`.  Shard counts that don't
    divide the leaf's dims degrade to 1, mirroring the replication fallback
    of ``parallel.sharding.spec_for_axes``.
    """
    if mode == "per_column":
        mode, block_n = "column_blocked", 1
    assert mode in ("structured", "column_blocked"), f"unknown mode {mode!r}"
    if mode == "column_blocked" and block_n < 1:
        raise ValueError("mode='column_blocked' needs block_n >= 1")
    if isinstance(params, dict) and "segments" not in params:
        return _pair_conv_tree(
            params, rounding, mode=mode, block_n=block_n,
            criterion=criterion, min_dim=min_dim,
        )

    specs = tuple(leaves) if leaves is not None else DEFAULT_PAIRED_LEAVES
    matched: set[tuple[str, str]] = set()
    leaves_report: list[LeafReport] = []

    def pair_stack(
        mats: np.ndarray, row_shards: int = 1, col_shards: int = 1
    ) -> tuple[dict[str, np.ndarray], int, tuple[int, ...] | None, int, int]:
        """Pair a (n, K, N) stack → (stacked metadata, weighted pair count,
        per-shard ledger, effective row/col shards)."""
        K, N = int(mats.shape[1]), int(mats.shape[2])
        rs = row_shards if row_shards > 1 and K % row_shards == 0 else 1
        cs = col_shards if col_shards > 1 and N % col_shards == 0 else 1
        if mode == "column_blocked":
            bn = min(block_n, N)
            if cs > 1 and (N // cs) % bn:
                cs = 1  # a shard boundary would split a block — keep whole
            ps_b = [
                pair_rows_blocked_sharded(
                    m, rounding, bn, criterion=criterion, row_shards=rs
                )
                for m in mats
            ]
            return (
                _stack_blocked(ps_b),
                sum(p.weighted_pairs for p in ps_b),
                _blocked_shard_ledger(ps_b, rs, cs),
                rs, cs,
            )
        # structured: pairs are whole rows, so a *column* split never cuts
        # them — only the contraction (row) axis needs the shard constraint
        ps_s = [
            pair_rows_structured_sharded(
                m, rounding, criterion=criterion, row_shards=rs
            )
            for m in mats
        ]
        return (
            _stack_structured(ps_s),
            sum(p.weighted_pairs for p in ps_s),
            _structured_shard_ledger(ps_s, rs) if rs > 1 else None,
            rs, 1,
        )

    def pair_segments(segments: list, prefix: str) -> list:
        new_segs = []
        for si, seg in enumerate(segments):
            new_seg = dict(seg)
            for sub_path, w_name in specs:
                sub = _resolve_sub(new_seg, sub_path)
                if sub is None or w_name not in sub:
                    continue
                matched.add((sub_path, w_name))
                arr = np.asarray(sub[w_name])
                if not jnp.issubdtype(arr.dtype, jnp.floating) or arr.ndim < 3:
                    continue  # stacked (layers, …) float matrices, bf16 included
                L = arr.shape[0]
                # MoE expert weights carry a second leading (expert) axis:
                # pair each expert's (K, F) matrix separately.
                expert = sub_path.split(".")[-1] == "moe" and arr.ndim == 4
                mat_shape = arr.shape[2:] if expert else arr.shape[1:]
                K, N = _lm_weight_matrix_shape(w_name, mat_shape)
                if K < min_dim or N < min_dim:
                    continue
                want_rs, want_cs = (1, 1)
                if shards is not None:
                    want_rs, want_cs = shards.get((sub_path, w_name), (1, 1))
                mats = arr.reshape(-1, K, N).astype(np.float64)
                meta, n_pairs, shard_pairs, rs, cs = pair_stack(
                    mats, want_rs, want_cs
                )
                if expert:
                    E = arr.shape[1]
                    meta = {
                        k: v.reshape(L, E, *v.shape[1:]) for k, v in meta.items()
                    }
                new_sub = dict(_resolve_sub(new_seg, sub_path))
                new_sub[w_name + "_pairing"] = meta
                _set_sub(new_seg, sub_path, new_sub)
                leaves_report.append(
                    LeafReport(
                        path=f"{prefix}[{si}].{sub_path}.{w_name}",
                        shape=tuple(arr.shape),
                        n_weights=int(mats.size),
                        n_pairs=int(n_pairs),
                        pair_fraction=2.0 * n_pairs / mats.size,
                        row_shards=rs,
                        col_shards=cs,
                        shard_pairs=shard_pairs,
                    )
                )
            new_segs.append(new_seg)
        return new_segs

    out = dict(params)
    out["segments"] = pair_segments(params.get("segments", []), "segments")
    enc = params.get("encoder")
    if isinstance(enc, dict) and isinstance(enc.get("segments"), list):
        enc = dict(enc)
        enc["segments"] = pair_segments(enc["segments"], "encoder.segments")
        out["encoder"] = enc

    unmatched = [s for s in specs if s not in matched]
    if leaves is not None and unmatched:
        raise ValueError(
            "pair_params: no weight matched leaf spec(s) "
            + ", ".join(f"{sp}.{wn}" for sp, wn in unmatched)
            + " — check the config's paired_leaves declaration against the "
            "param tree (sub-blocks present: "
            + ", ".join(sorted({
                k for seg in params.get("segments", [])
                for k, v in seg.items() if isinstance(v, dict)
            }))
            + ")"
        )
    if not leaves_report:
        raise ValueError(
            "pair_params: no pairing-eligible weights found — looked for "
            + ", ".join(f"{sp}.{wn}" for sp, wn in specs)
            + " among stacked float matrices with GEMM dims >= "
            f"{min_dim}; tree carries sub-blocks "
            + ", ".join(sorted({
                k for seg in params.get("segments", [])
                for k, v in seg.items() if isinstance(v, dict)
            }))
        )
    report = PairedModelReport(rounding=rounding, mode=mode, leaves=leaves_report)
    return out, report


def pair_lm_params(
    params: Any,
    rounding: float,
    *,
    mode: str = "structured",
    block_n: int = 0,
    criterion: str = "rms",
    min_dim: int = 8,
    shards: Any = None,
) -> tuple[Any, PairedModelReport]:
    """Backward-compatible LM entry point: :func:`pair_params` in auto mode.

    Pairs whatever subset of :data:`DEFAULT_PAIRED_LEAVES` the tree carries
    (a plain GQA tree yields exactly the :data:`LM_PAIRED_WEIGHTS` seven);
    raises if nothing matches at all.
    """
    return pair_params(
        params, rounding, mode=mode, block_n=block_n,
        criterion=criterion, min_dim=min_dim, shards=shards,
    )


def pair_model_params(
    params: Any,
    rounding: float,
    *,
    mode: str = "per_column",
    block_n: int = 0,
    min_dim: int = 8,
    predicate: Callable[[str, np.ndarray], bool] | None = None,
    keep_pairings: bool = False,
) -> tuple[Any, PairedModelReport]:
    """Pair every eligible weight leaf of ``params``.

    Eligible = float array, ndim in (2, 4), both contraction dims >= min_dim,
    and ``predicate(path, leaf)`` (if given) returns True.  4-D leaves are
    treated as conv kernels (H, W, Cin, Cout) and paired per filter, exactly
    as the paper does for LeNet-5; 2-D leaves (K, N) are paired per column
    (= per output neuron).

    ``mode`` picks the pairing spectrum point: ``"per_column"`` (the paper's
    Algorithm 1, default), ``"structured"`` (one shared-row pairing per
    leaf — the original TPU kernel layout), or ``"column_blocked"`` (one
    shared-row pairing per ``block_n`` output columns — the kernel-executable
    mode that closes most of the structured-vs-per-column pairing gap;
    requires ``block_n >= 1``).

    Returns (paired_params, report).  ``paired_params`` has the same treedef;
    only eligible leaves are replaced by their folded equivalents.
    """
    if mode == "column_blocked" and block_n < 1:
        raise ValueError("mode='column_blocked' needs block_n >= 1")
    leaves_report: list[LeafReport] = []

    def handle(path, leaf):
        if not isinstance(leaf, np.ndarray | jax.Array):
            return leaf
        arr = np.asarray(leaf)
        if not jnp.issubdtype(arr.dtype, jnp.floating) or arr.ndim not in (2, 4):
            return leaf  # bf16 included (numpy reports its kind as "V")
        if arr.ndim == 4:
            H, Wd, Cin, Cout = arr.shape
            mat = arr.reshape(H * Wd * Cin, Cout)
        else:
            mat = arr
        if mat.shape[0] < min_dim or mat.shape[1] < min_dim:
            return leaf
        pstr = _path_str(path)
        if predicate is not None and not predicate(pstr, arr):
            return leaf

        mat64 = mat.astype(np.float64)
        if mode == "per_column":
            cp = pair_columns(mat64, rounding)
            folded = fold_columns(mat64, cp)
            n_pairs = cp.total_pairs
            pairing: ColumnPairing | StructuredPairing | BlockedPairing = cp
        elif mode == "structured":
            sp = pair_rows_structured(mat64, rounding)
            folded = sp.fold()
            n_pairs = sp.weighted_pairs  # one pair row spans N columns
            pairing = sp
        elif mode == "column_blocked":
            bp = pair_rows_blocked(mat64, rounding, block_n)
            folded = bp.fold()
            n_pairs = bp.weighted_pairs  # per-column-equivalent count
            pairing = bp
        else:
            raise ValueError(f"unknown mode {mode!r}")

        leaves_report.append(
            LeafReport(
                path=pstr,
                shape=tuple(arr.shape),
                n_weights=int(mat.size),
                n_pairs=int(n_pairs),
                pair_fraction=2.0 * n_pairs / mat.size,
                pairing=pairing if keep_pairings else None,
            )
        )
        return folded.reshape(arr.shape).astype(arr.dtype)

    paired = jax.tree_util.tree_map_with_path(handle, params)
    report = PairedModelReport(rounding=rounding, mode=mode, leaves=leaves_report)
    return paired, report
