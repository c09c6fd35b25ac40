"""Mesh-sharded paired decode: token parity + per-shard ledger equality.

The distributed claim of the subtractor path is locality: tensor-parallel
splits of the projection weights cut across per-column pairing blocks, so the
``(Pmax, Rmax)`` metadata must be *built per shard* (no pair crosses a shard
boundary) and *placed beside its weight shard* — never regathered inside the
decode loop.  This bench gates both halves numerically:

1. **r = 0 token parity** — a 2×N mesh ServeEngine (CI runs it 2×4 under
   ``XLA_FLAGS=--xla_force_host_platform_device_count=8``) decodes the same
   prompts token-for-token as the single-host engine.  At r = 0 the paired
   kernel is exact, so any divergence is a sharding bug, not rounding.
2. **r = 0.05 ledger equality** — for every leaf the shard-aware build
   reports, the per-shard pair ledger must sum to the leaf's total; for
   column-sharded leaves (block-aligned splits don't constrain per-column
   pairing) the total must equal the single-host build's; and for one
   representative column-sharded (wq) and row-sharded (w_down) leaf the
   per-shard counts must equal *standalone* pairings of the corresponding
   weight slices — per-shard metadata is exactly what each device would have
   built from its local rows/columns.

The placement half (zero resharding of metadata inside the decode while-loop)
is the ``sharded_decode`` analysis target's job; this bench covers the math.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np

from benchmarks.common import fmt_table, write_result
from repro.configs import get_smoke_config
from repro.core.pairing import pair_rows_blocked
from repro.core.transform import pair_params, tp_shard_plan
from repro.launch.steps import abstract_params
from repro.models import lm as M
from repro.models.param import unzip
from repro.parallel.rules import rules_for
from repro.parallel.sharding import auto_mesh
from repro.serving.engine import ServeEngine

LEDGER_ROUNDING = 0.05


def _knobs(rounding: float) -> M.PerfKnobs:
    return M.PerfKnobs(
        q_chunk=16, k_chunk=16, remat="none",
        gemm="pallas_paired", pair_block_n=1, pair_rounding=rounding,
    )


def _gemm_stack(seg: dict, sub: str, name: str) -> np.ndarray:
    """(L, K, N) float64 GEMM view of one stacked decoder leaf."""
    arr = np.asarray(seg[sub][name], np.float64)
    L = arr.shape[0]
    if name == "wo":
        K = int(np.prod(arr.shape[1:-1]))
        return arr.reshape(L, K, arr.shape[-1])
    return arr.reshape(L, arr.shape[1], -1)


def _standalone_shard_ledger(
    mats: np.ndarray, rounding: float, rs: int, cs: int
) -> list[int]:
    """Per-shard weighted per-column pair counts from *standalone* builds on
    each shard's weight slice — the independent reference the shard-aware
    build's ledger must reproduce exactly."""
    L, K, N = mats.shape
    n_shards = max(rs, cs)
    totals = [0] * n_shards
    for m in mats:
        for s in range(n_shards):
            if cs > 1:
                sl = m[:, s * (N // cs):(s + 1) * (N // cs)]
            else:
                sl = m[s * (K // rs):(s + 1) * (K // rs), :]
            totals[s] += pair_rows_blocked(sl, rounding, 1).weighted_pairs
    return totals


def run(quick: bool = False) -> dict:
    n_dev = jax.device_count()
    mesh_shape = (2, n_dev // 2) if n_dev >= 4 else (1, n_dev)
    mesh = auto_mesh(mesh_shape, ("data", "model"))
    cfg = dataclasses.replace(get_smoke_config("qwen2-1.5b"), dtype="float32")
    params, _ = unzip(M.init_lm(cfg, jax.random.key(0)))
    failures: list[str] = []

    # -- 1) r=0 token parity: mesh engine vs single-host engine -------------
    n_steps = 4 if quick else 10
    rng = np.random.default_rng(0)
    prompts = {
        0: rng.integers(1, cfg.vocab, size=7).astype(np.int32),
        1: rng.integers(1, cfg.vocab, size=12).astype(np.int32),
    }
    ref = ServeEngine(cfg, params, max_seq=32, batch_size=2, knobs=_knobs(0.0))
    out_ref = ref.generate(dict(prompts), n_steps)
    t0 = time.time()
    eng = ServeEngine(
        cfg, params, max_seq=32, batch_size=2, knobs=_knobs(0.0), mesh=mesh
    )
    t_wire = time.time() - t0
    out_mesh = eng.generate(dict(prompts), n_steps)
    t0 = time.time()
    eng.step()
    t_step = time.time() - t0
    for slot in prompts:
        if out_ref[slot] != out_mesh[slot]:
            failures.append(
                f"r=0 token mismatch slot {slot}: single-host "
                f"{out_ref[slot]} vs mesh {out_mesh[slot]}"
            )

    # -- 2) r=0.05 per-shard ledger equality --------------------------------
    rules = rules_for(cfg, "decode", mesh)
    _, param_axes = abstract_params(cfg)
    plan = tp_shard_plan(
        param_axes, params, mesh, rules, leaves=cfg.paired_leaves
    )
    _, rep_mesh = pair_params(
        params, LEDGER_ROUNDING, mode="per_column",
        leaves=cfg.paired_leaves, shards=plan,
    )
    _, rep_single = pair_params(
        params, LEDGER_ROUNDING, mode="per_column", leaves=cfg.paired_leaves
    )
    single_by_path = {lr.path: lr for lr in rep_single.leaves}
    rows = []
    for lr in rep_mesh.leaves:
        single = single_by_path[lr.path]
        if lr.shard_pairs is not None and sum(lr.shard_pairs) != lr.n_pairs:
            failures.append(
                f"{lr.path}: shard ledger {lr.shard_pairs} sums to "
                f"{sum(lr.shard_pairs)} != total {lr.n_pairs}"
            )
        if lr.col_shards > 1 and lr.n_pairs != single.n_pairs:
            # a block-aligned column split never constrains per-column
            # pairing — the sharded total must equal the single-host total
            failures.append(
                f"{lr.path}: column-sharded total {lr.n_pairs} != "
                f"single-host {single.n_pairs}"
            )
        rows.append({
            "leaf": lr.path.split("].")[-1],
            "rs": lr.row_shards,
            "cs": lr.col_shards,
            "pairs": lr.n_pairs,
            "single_host": single.n_pairs,
            "pair_frac": lr.pair_fraction,
        })

    # -- 3) per-shard == standalone slice builds (wq column / w_down row) ---
    seg = params["segments"][0]
    slice_checks = []
    for sub, name in (("attn", "wq"), ("mlp", "w_down")):
        rs, cs = plan[(sub, name)]
        lr = next(
            l for l in rep_mesh.leaves if l.path.endswith(f"{sub}.{name}")
        )
        if max(rs, cs) > 1:
            want = _standalone_shard_ledger(
                _gemm_stack(seg, sub, name), LEDGER_ROUNDING, rs, cs
            )
            got = list(lr.shard_pairs or ())
            if got != want:
                failures.append(
                    f"{sub}.{name}: per-shard ledger {got} != standalone "
                    f"slice builds {want}"
                )
            slice_checks.append(
                {"leaf": f"{sub}.{name}", "rs": rs, "cs": cs,
                 "per_shard": got, "standalone": want}
            )

    print(fmt_table(
        rows, ["leaf", "rs", "cs", "pairs", "single_host", "pair_frac"],
        f"mesh_decode r={LEDGER_ROUNDING} shard ledger "
        f"(mesh {mesh_shape[0]}x{mesh_shape[1]})",
    ))
    sharded_leaves = sum(1 for r in rows if r["rs"] > 1 or r["cs"] > 1)
    print(
        f"[mesh_decode] {n_dev} device(s) as {mesh_shape}; r=0 parity over "
        f"{n_steps} steps x {len(prompts)} slots; {sharded_leaves}/{len(rows)}"
        f" leaves shard-built; wire {t_wire:.1f}s, decode step {t_step*1e3:.0f}ms"
    )

    payload = {
        "mesh": list(mesh_shape),
        "devices": n_dev,
        "rounding": LEDGER_ROUNDING,
        "parity_steps": n_steps,
        "parity_ok": not any("token mismatch" in f for f in failures),
        "ledger": rows,
        "slice_checks": slice_checks,
        "wire_seconds": t_wire,
        "decode_step_seconds": t_step,
        "failures": failures,
    }
    write_result("mesh_decode", payload)
    if failures:
        raise AssertionError("; ".join(failures))
    return {"perf_summary": payload}
