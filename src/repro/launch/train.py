"""Distributed training driver.

Runs the pjit train step on whatever mesh is available — production meshes
in a real fleet, or a small host-device mesh for local validation:

    # real (or forced-host-device) cluster
    python -m repro.launch.train --arch qwen2-1.5b --steps 100 ...

    # local CPU validation with a reduced config
    python -m repro.launch.train --arch qwen2-1.5b --smoke --steps 20

Fault tolerance: checkpoints (params, opt_state) every --ckpt-every steps
with atomic rename; on restart the loop resumes from the newest checkpoint
and regenerates the deterministic data stream from the step counter, so a
killed job continues bit-identically.  Elasticity: the mesh shape is an
argument — rerunning with a different shape re-shards the same logical rules
onto the new topology (the checkpoint stores plain host arrays, which are
re-placed by pjit on load).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config, get_smoke_config
from repro.data.tokens import token_batches
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.steps import build_train_step
from repro.models import lm as M
from repro.models.param import unzip
from repro.parallel.rules import rules_for
from repro.parallel.sharding import auto_mesh, shardings_for
from repro.train.checkpoint import latest_step, restore_checkpoint, save_checkpoint
from repro.train.optimizer import adamw, cosine_schedule


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--mesh", default="", help="e.g. 2x2 → axes (data, model)")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--paired-rounding", type=float, default=0.0,
                    help="apply the paper's weight pairing before training "
                    "(demonstrates pairing-aware finetune)")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)

    if args.mesh:
        shape = tuple(int(x) for x in args.mesh.split("x"))
        names = ("data", "model")[: len(shape)]
        mesh = auto_mesh(shape, names)
    else:
        mesh = auto_mesh((jax.device_count(), 1), ("data", "model"))
    rules = rules_for(cfg, "train", mesh)

    tree = M.init_lm(cfg, jax.random.key(0))
    params, axes = unzip(tree)
    if args.paired_rounding > 0:
        from repro.core.transform import pair_model_params

        params, report = pair_model_params(params, args.paired_rounding)
        print(f"[train] paired {report.total_pairs} weight pairs "
              f"({100*report.pair_fraction:.1f}% of weights) "
              f"→ modeled savings {report.savings()}")

    opt = adamw(cosine_schedule(args.lr, args.steps, warmup_steps=min(100, args.steps // 10)))
    opt_state = opt.init(params)

    start = 0
    if args.ckpt_dir and latest_step(args.ckpt_dir) is not None:
        (params, opt_state), meta = restore_checkpoint(args.ckpt_dir, (params, opt_state))
        start = meta["step"]
        print(f"[train] resumed from step {start}")

    p_shard = shardings_for(axes, mesh, rules, params)
    step_fn = build_train_step(cfg, opt, M.PerfKnobs(q_chunk=min(1024, args.seq)), mesh, rules)
    jitted = jax.jit(
        step_fn,
        in_shardings=(p_shard, {k: p_shard for k in opt_state}, None, None),
        out_shardings=(p_shard, {k: p_shard for k in opt_state}, None),
        donate_argnums=(0, 1),
    )

    data = token_batches(args.batch, args.seq, cfg.vocab, seed=1, start_step=start)
    t0 = time.time()
    with jax.set_mesh(mesh):
        for i, (tok, lab) in enumerate(data, start=start):
            if i >= args.steps:
                break
            batch = {"tokens": jnp.asarray(tok), "labels": jnp.asarray(lab)}
            if cfg.vision_prefix:
                batch["patches"] = jnp.zeros(
                    (args.batch, cfg.vision_prefix, cfg.vision_embed_dim), cfg.dtype
                )
            if cfg.encoder is not None:
                batch["frames"] = jnp.zeros(
                    (args.batch, cfg.encoder.frames, cfg.d_model), cfg.dtype
                )
            params, opt_state, metrics = jitted(params, opt_state, jnp.int32(i), batch)
            if args.log_every and (i + 1) % args.log_every == 0:
                m = jax.tree.map(float, metrics)
                print(f"[train] step {i+1} loss {m['loss']:.4f} xent {m['xent']:.4f} "
                      f"({(i+1-start)/(time.time()-t0):.2f} it/s)")
            if args.ckpt_dir and args.ckpt_every and (i + 1) % args.ckpt_every == 0:
                save_checkpoint(args.ckpt_dir, i + 1, (params, opt_state),
                                metadata={"step": i + 1})
    print(f"[train] done: {args.steps - start} steps in {time.time()-t0:.1f}s, "
          f"final loss {float(metrics['loss']):.4f}")


if __name__ == "__main__":
    main()
