"""Distributed serving driver: prefill + batched greedy decode on a mesh.

    # local CPU validation with a reduced config (+ the paper's pairing)
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --smoke \
        --paired-rounding 0.01 --steps 16

    # hardened front end: Poisson load + chaos over the paired engine, with
    # graceful degradation to the unpaired fallback path
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --smoke \
        --gemm pallas_paired --frontend --arrival-rate 20 --horizon 0.5 \
        --inject nan_logits:0.05,kv_poison:0.02,kernel_failure:0.02

On a real fleet the same `serve_step` lowers against the production mesh
(see launch/dryrun.py decode cells: cache sequence-sharded over `model`,
batch over `data`); here the ServeEngine drives it on local devices.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.core.transform import pair_model_params
from repro.launch.compile_cache import enable_compile_cache
from repro.models import lm as M
from repro.models.lenet import CONV_IMPLS
from repro.models.param import unzip
from repro.serving.engine import ServeEngine


def serving_params(cfg, seed: int = 0):
    """Seeded weights as the server holds them: weight matrices in the
    config's compute dtype (``cfg.dtype``), vectors (norm scales, biases)
    in fp32.  Made op by op: one jitted init of a full-depth tree takes
    minutes to compile."""
    cdt = jnp.dtype(cfg.dtype)
    params, _ = unzip(M.init_lm(cfg, jax.random.key(seed)))
    return jax.tree.map(
        lambda a: a.astype(cdt)
        if a.ndim >= 2 and jnp.issubdtype(a.dtype, jnp.floating) else a,
        params,
    )


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--paired-rounding", type=float, default=0.0)
    ap.add_argument("--pair-block-n", type=int, default=0,
                    help="pairing-mode spectrum: 0 → the paper's per-column "
                         "pairing for weight folding (and structured pairing "
                         "for kernel artifacts); n >= 1 → column-blocked "
                         "pairing with one shared-row pairing per n output "
                         "channels (kernel-executable; 1 == per-column)")
    ap.add_argument("--gemm", choices=("xla", "pallas", "pallas_paired"),
                    default="xla",
                    help="route layer GEMMs through the fused K-tiled "
                         "Pallas kernel (interpret mode off-TPU); "
                         "pallas_paired runs the decoder qkv/out-proj/MLP "
                         "GEMMs on the subtractor kernel with the sublayer "
                         "residual adds fused into the epilogue "
                         "(see --pair-rounding / --pair-block-n)")
    ap.add_argument("--pair-rounding", type=float, default=0.0,
                    help="rounding size for the pallas_paired LM pairing "
                         "artifacts (live-weight kernel path, distinct from "
                         "--paired-rounding's offline weight folding); 0.0 "
                         "is the exact-parity point")
    ap.add_argument("--attn", choices=("xla", "pallas_fused"), default="xla",
                    help="decode attention lowering: xla runs the dense "
                         "reference; pallas_fused runs the single-token "
                         "Pallas decode-attention kernel whose attended "
                         "output feeds the paired out-projection epilogue "
                         "directly (one fewer HBM writeback per layer)")
    ap.add_argument("--conv", choices=CONV_IMPLS, default="xla",
                    help="conv lowering for conv-bearing models: plain "
                         "lax.conv, im2col patch GEMM, or the paired "
                         "subtractor kernel (no-op for the pure-LM archs)")
    ap.add_argument("--fuse-pool", action="store_true",
                    help="conv→pool megakernel: absorb 2x2 max-pools into "
                         "the paired-conv epilogue (--conv pallas_paired "
                         "only; one HBM writeback per conv layer)")
    ap.add_argument("--block-k", type=int, default=0,
                    help="Pallas GEMM k-tile; 0 → tile cache / heuristic")
    ap.add_argument("--tile-cache", default="",
                    help="path to a persisted kernel TileCache "
                         "(benchmarks/roofline.py writes one); measured "
                         "tile configs there beat the VMEM heuristic")
    # -- hardened front end (serving.frontend) -------------------------------
    ap.add_argument("--frontend", action="store_true",
                    help="drive the engine through the async front end: "
                         "seeded Poisson arrivals, length-bucketed admission, "
                         "chunked prefill, numeric watchdog with degradation "
                         "to the unpaired fallback engine")
    ap.add_argument("--arrival-rate", type=float, default=10.0,
                    help="offered load in requests per virtual second")
    ap.add_argument("--horizon", type=float, default=1.0,
                    help="arrival window in virtual seconds")
    ap.add_argument("--seed", type=int, default=0,
                    help="workload + fault-schedule seed")
    ap.add_argument("--prefill-chunk", type=int, default=8,
                    help="prompt tokens per monolithic prefill; the tail of "
                         "longer prompts rides the shared decode steps")
    ap.add_argument("--deadline", type=float, default=float("inf"),
                    help="per-request completion deadline (virtual s)")
    ap.add_argument("--fallback-gemm", choices=("xla", "pallas"), default="xla",
                    help="unpaired exact path quarantined requests degrade to")
    ap.add_argument("--inject", default="",
                    help="fault rates, e.g. 'nan_logits:0.05,kv_poison:0.02' "
                         "(per front-end step; see serving.faults.FAULT_KINDS)")
    args = ap.parse_args()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    params = serving_params(cfg)
    if args.paired_rounding > 0:
        mode = "column_blocked" if args.pair_block_n >= 1 else "per_column"
        params, report = pair_model_params(
            params, args.paired_rounding, min_dim=4,
            mode=mode, block_n=args.pair_block_n,
        )
        s = report.savings()
        print(f"[serve] subtractor pairing ({mode}"
              f"{f', block_n={args.pair_block_n}' if args.pair_block_n else ''}): "
              f"{report.total_pairs} pairs "
              f"({100*report.pair_fraction:.1f}% of weights) → modeled "
              f"power −{100*s['power_saving']:.1f}%, area −{100*s['area_saving']:.1f}%")

    knobs = M.PerfKnobs(q_chunk=32, k_chunk=32, remat="none",
                        gemm=args.gemm, attn=args.attn, conv=args.conv,
                        block_k=args.block_k,
                        fuse_pool=args.fuse_pool, tile_cache=args.tile_cache,
                        pair_block_n=args.pair_block_n,
                        pair_rounding=args.pair_rounding)
    eng = ServeEngine(cfg, params, max_seq=args.max_seq, batch_size=args.batch, knobs=knobs)
    if eng.pair_report is not None:
        rp = eng.pair_report
        print(f"[serve] paired-kernel LM path ({rp.mode}"
              f"{f', block_n={args.pair_block_n}' if args.pair_block_n else ''}"
              f", rounding {args.pair_rounding}): "
              f"{rp.total_pairs} per-column-equivalent pairs across "
              f"{len(rp.leaves)} decoder weights "
              f"({100 * rp.pair_fraction:.1f}% of paired-eligible weights); "
              f"residual adds fused into the kernel epilogue")
    if args.frontend:
        _run_frontend(args, cfg, params, eng)
        return

    rng = np.random.default_rng(0)
    prompts = {
        i: rng.integers(0, cfg.vocab, size=(8 + 4 * i,)).astype(np.int32)
        for i in range(args.batch)
    }
    t0 = time.time()
    outs = eng.generate(prompts, args.steps)
    dt = time.time() - t0
    for slot, toks in outs.items():
        print(f"[serve] slot {slot}: prompt {len(prompts[slot])} toks → {toks}")
    print(f"[serve] {args.batch * args.steps} tokens in {dt:.2f}s "
          f"({args.batch * args.steps / dt:.1f} tok/s incl. prefill)")


def _parse_fault_rates(spec: str) -> dict[str, float]:
    rates: dict[str, float] = {}
    for part in filter(None, (p.strip() for p in spec.split(","))):
        kind, _, rate = part.partition(":")
        rates[kind] = float(rate or 0.0)
    return rates


def _run_frontend(args, cfg, params, eng: ServeEngine) -> None:
    """Simulated-load run: Poisson arrivals + optional chaos, degrading to a
    fresh unpaired fallback engine built from the same (unpaired) weights."""
    import json

    from repro.serving import (
        FaultInjector,
        FrontendConfig,
        ServeFrontend,
        poisson_workload,
    )

    # `params` is the pre-pairing tree (ServeEngine pairs its own copy), so
    # the fallback engine runs plain exact GEMMs with no metadata siblings
    fb_knobs = dataclasses.replace(
        eng.knobs, gemm=args.fallback_gemm, pair_rounding=0.0)
    fallback = ServeEngine(cfg, params, max_seq=args.max_seq,
                           batch_size=args.batch, knobs=fb_knobs)
    workload = poisson_workload(
        rate_rps=args.arrival_rate, horizon_s=args.horizon, seed=args.seed,
        vocab=cfg.vocab, prompt_len=(3, max(4, args.max_seq // 4)),
        new_tokens=(2, max(3, args.steps)),
    )
    faults = None
    rates = _parse_fault_rates(args.inject)
    if rates:
        faults = FaultInjector.from_rates(
            args.seed, n_steps=4096, batch_size=args.batch, rates=rates)
    fe = ServeFrontend(
        eng, fallback,
        FrontendConfig(prefill_chunk=args.prefill_chunk,
                       deadline_s=args.deadline),
        faults=faults,
    )
    report = fe.run(workload, offered_load_rps=args.arrival_rate)
    print(f"[serve] front end: {len(workload)} requests @ "
          f"{args.arrival_rate} req/s over {args.horizon}s "
          f"({len(report.incidents)} incident records)")
    print(json.dumps(report.summary(), indent=2))
    lost = report.lost()
    if lost:
        raise SystemExit(f"[serve] LOST {len(lost)} request(s): "
                         f"{[r.rid for r in lost]}")


if __name__ == "__main__":
    main()
