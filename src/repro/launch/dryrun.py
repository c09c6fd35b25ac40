import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove the distribution config is coherent on the
production mesh without real hardware.

For every (architecture × input shape × mesh) cell this lowers + compiles
the real step function — train_step for train shapes, prefill_step for
prefill, serve_step for decode — against ShapeDtypeStruct inputs (no
allocation), then records:

* ``memory_analysis()``  — per-device bytes (proves the config fits HBM),
* ``cost_analysis()``    — per-device HLO FLOPs / bytes (roofline terms 1+2),
* parsed collective traffic from the compiled HLO (roofline term 3).

The two XLA_FLAGS lines above MUST run before any other import (jax locks
the device count on first init); smoke tests and benches import jax normally
and see one device.

Usage:
    python -m repro.launch.dryrun --arch qwen2-1.5b --shape train_4k
    python -m repro.launch.dryrun --arch all --shape all --multi-pod both
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import jax

from repro.configs import ALL_ARCHS, get_config
from repro.configs.base import SHAPES, supports_shape
from repro.launch.inputs import batch_specs
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import wire_cell
from repro.models.lm import PerfKnobs
from repro.parallel.hlo import analyze
from repro.parallel.sharding import record_spec_fallbacks

RESULTS_DIR = Path(__file__).resolve().parents[3] / "benchmarks" / "results" / "dryrun"


def input_specs(arch: str, shape_name: str):
    """ShapeDtypeStruct stand-ins for every model input of one cell."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    return batch_specs(cfg, shape.global_batch, shape.seq_len, shape.kind)


def run_cell(
    arch: str,
    shape_name: str,
    multi_pod: bool,
    knobs: PerfKnobs | None = None,
    *,
    save: bool = True,
    tag: str = "",
) -> dict:
    if knobs is None:
        knobs = PerfKnobs()
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    cell_id = f"{arch}__{shape_name}__{mesh_name}" + (f"__{tag}" if tag else "")

    ok, why = supports_shape(cfg, shape)
    if not ok:
        out = {"cell": cell_id, "status": "skipped", "reason": why}
        print(json.dumps(out))
        if save:
            _save(cell_id, out)
        return out

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    try:
        # every spec_for_axes replication fallback taken while wiring +
        # lowering this cell (divisibility, mesh-axis contention) lands in
        # the record — silent degradation is a config bug until audited
        with record_spec_fallbacks() as fallbacks:
            cell = wire_cell(
                cfg, mesh,
                seq_len=shape.seq_len,
                global_batch=shape.global_batch,
                mode=shape.kind,
                knobs=knobs,
            )
            with jax.set_mesh(mesh):
                lowered = cell.lower()
                t_lower = time.time() - t0
                compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis() or {}
        hlo = compiled.as_text()
        # trip-count-aware HLO accounting (xla cost_analysis counts scan
        # bodies once — see parallel/hlo.py)
        scopes = ("flash_vmem",) if knobs.attn_fused else ()
        hc = analyze(hlo, fused_scopes=scopes)
        attn_bytes = 0.0
        if knobs.attn_fused:
            attn_bytes = fused_attention_hbm_bytes(cfg, shape, mesh, knobs)
            hc.hbm_bytes += attn_bytes
        coll = hc.collective

        # pairing buffers are loop-invariant decode state — lint the compiled
        # HLO for reshards/copies of them inside the while loop (error-severity
        # findings make the cell record visibly dirty without failing the run)
        from repro.analysis import RuleContext, run_rules

        lint = run_rules(
            RuleContext(target=cell_id, hlo_text=hlo),
            rule_ids=("hlo/pairing-resharding-in-loop",),
        )

        n_chips = mesh.devices.size
        out = {
            "cell": cell_id,
            "status": "ok",
            "arch": arch,
            "shape": shape_name,
            "mode": shape.kind,
            "mesh": list(mesh.devices.shape),
            "n_chips": int(n_chips),
            "knobs": vars(knobs) if hasattr(knobs, "__dict__") else dataclass_dict(knobs),
            "seconds": {"lower": round(t_lower, 1), "compile": round(t_compile, 1)},
            "memory": {
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "alias_bytes": mem.alias_size_in_bytes,
                "peak_device_bytes": mem.argument_size_in_bytes
                + mem.output_size_in_bytes
                + mem.temp_size_in_bytes
                - mem.alias_size_in_bytes,
            },
            "cost": {
                "flops": hc.flops,
                "bytes_accessed": hc.hbm_bytes,
                "attn_fused_model_bytes": attn_bytes,
                "xla_flops_unscaled": cost.get("flops", 0.0),
                "xla_bytes_unscaled": cost.get("bytes accessed", 0.0),
            },
            "collectives": coll,
            "sharding_fallbacks": [
                {"axis": axis, "reason": reason, "count": count}
                for (axis, reason), count in fallbacks.items()
            ],
            "analysis": {
                "errors": len(lint.errors()),
                "findings": [f.as_dict() for f in lint.findings],
            },
            "model": {
                "params": cfg.param_count(),
                "params_active": cfg.param_count(active_only=True),
            },
        }
        # useful-compute cross-check: 6·N·D (train) or 2·N·D (decode)
        tokens_per_chip = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1) / n_chips
        mf = (6.0 if shape.kind == "train" else 2.0) * cfg.param_count(active_only=True) * tokens_per_chip
        out["model"]["model_flops_per_chip"] = mf
        out["model"]["useful_flops_ratio"] = mf / hc.flops if hc.flops else 0.0
        print(
            f"[dryrun] {cell_id}: OK lower {t_lower:.0f}s compile {t_compile:.0f}s | "
            f"peak/device {out['memory']['peak_device_bytes']/2**30:.2f} GiB | "
            f"flops/device {out['cost']['flops']:.3e} (useful {out['model']['useful_flops_ratio']:.2f}) | "
            f"coll {coll['total_bytes']/2**20:.1f} MiB"
        )
    except Exception as e:  # noqa: BLE001 — a failing cell is a bug we record
        out = {
            "cell": cell_id,
            "status": "failed",
            "error": f"{type(e).__name__}: {e}",
            "traceback": traceback.format_exc()[-4000:],
        }
        print(f"[dryrun] {cell_id}: FAILED {type(e).__name__}: {e}")

    if save:
        _save(cell_id, out)
    return out


def dataclass_dict(k):
    import dataclasses

    return dataclasses.asdict(k)


def fused_attention_hbm_bytes(cfg, shape, mesh, knobs: PerfKnobs) -> float:
    """Per-chip HBM traffic of the Pallas flash kernel, modeled from shapes.

    The kernel streams Q once, writes O once, and re-reads K/V once per
    q-block (causal skipping → on average (nq+1)/2 of them).  For train
    cells the remat schedule runs the forward twice and the backward reads
    ~2x the forward, so traffic ≈ 4x forward.  MLA uses the materialised
    per-head K (nope+rope) / padded V.  SSM layers have no attention.
    """
    if cfg.attention_kind == "none" or shape.kind == "decode":
        return 0.0
    names = mesh.axis_names
    model = mesh.shape["model"] if "model" in names else 1
    data = 1
    for a in ("pod", "data"):
        if a in names:
            data *= mesh.shape[a]
    B_loc = max(1, shape.global_batch // data)
    S = shape.seq_len + cfg.meta_tokens
    qc = min(knobs.q_chunk, S)
    nq = -(-S // qc)
    H, KH = cfg.n_heads, cfg.n_kv_heads
    if cfg.mla is not None:
        hd_q = cfg.mla.qk_nope_dim + cfg.mla.qk_rope_dim
        hd_kv = hd_q  # k materialised per head; v padded to the same width
        KH = H
    else:
        hd_q = hd_kv = cfg.head_dim
    H_loc = H // model if H % model == 0 else H
    KH_loc = KH // model if KH % model == 0 else KH
    bpe = 2  # bf16
    q_o = 2 * B_loc * S * H_loc * hd_q * bpe
    kv = 2 * B_loc * S * KH_loc * hd_kv * bpe
    kv_reads = kv * (nq + 1) / 2  # causal-skip average
    n_attn = sum(1 for i in range(cfg.n_layers) if cfg.layer_kind(i) != "ssm")
    if cfg.encoder is not None:
        n_attn += cfg.encoder.n_layers  # bidirectional: full nk — approximate
    passes = 4.0 if shape.kind == "train" else 1.0
    return n_attn * (q_o + kv_reads) * passes


def _save(cell_id: str, payload: dict) -> None:
    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    (RESULTS_DIR / f"{cell_id}.json").write_text(json.dumps(payload, indent=2))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all", help="arch id or 'all'")
    ap.add_argument("--shape", default="all", help="shape name or 'all'")
    ap.add_argument(
        "--multi-pod", default="both", choices=["true", "false", "both"],
        help="single-pod (16x16), multi-pod (2x16x16), or both",
    )
    ap.add_argument("--q-chunk", type=int, default=1024)
    ap.add_argument("--k-chunk", type=int, default=1024)
    ap.add_argument("--remat", default="full", choices=["full", "dots", "none"])
    ap.add_argument("--attn-fused", action="store_true",
                    help="account flash-attention interiors as VMEM-fused "
                    "(Pallas kernel target; adds modeled boundary traffic)")
    ap.add_argument("--skip-done", action="store_true", help="skip cells with saved results")
    ap.add_argument("--tag", default="", help="suffix for result files (perf experiments)")
    args = ap.parse_args()

    archs = ALL_ARCHS if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    pods = {"true": [True], "false": [False], "both": [False, True]}[args.multi_pod]
    knobs = PerfKnobs(q_chunk=args.q_chunk, k_chunk=args.k_chunk, remat=args.remat,
                      attn_fused=args.attn_fused)

    n_ok = n_fail = n_skip = 0
    for arch in archs:
        for shape in shapes:
            for mp in pods:
                mesh_name = "pod2x16x16" if mp else "pod16x16"
                cell_id = f"{arch}__{shape}__{mesh_name}" + (f"__{args.tag}" if args.tag else "")
                if args.skip_done and (RESULTS_DIR / f"{cell_id}.json").exists():
                    prev = json.loads((RESULTS_DIR / f"{cell_id}.json").read_text())
                    if prev.get("status") in ("ok", "skipped"):
                        continue
                out = run_cell(arch, shape, mp, knobs, tag=args.tag)
                n_ok += out["status"] == "ok"
                n_fail += out["status"] == "failed"
                n_skip += out["status"] == "skipped"
    print(f"[dryrun] done: {n_ok} ok, {n_fail} failed, {n_skip} skipped")


if __name__ == "__main__":
    main()
