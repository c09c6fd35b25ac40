"""End-to-end driver: train a ~100M-param qwen2-family model for a few
hundred steps on the deterministic token stream, with checkpoint/resume.

This uses the same pjit step as the production launcher, on a local
(device_count, 1) mesh.  Loss must fall well below log(vocab) — the stream
has learnable bigram structure.

Run:  PYTHONPATH=src python examples/lm_train.py [--steps 300]
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.data.tokens import token_batches
from repro.launch.steps import build_train_step
from repro.models import lm as M
from repro.models.param import unzip
from repro.parallel.rules import rules_for
from repro.parallel.sharding import auto_mesh
from repro.train.optimizer import adamw, cosine_schedule


def config_100m() -> ModelConfig:
    """~100M params, qwen2 family (GQA + QKV bias, tied embeddings)."""
    return ModelConfig(
        name="qwen2-100m", family="dense",
        n_layers=8, d_model=512, n_heads=8, n_kv_heads=2,
        d_ff=2048, vocab=8192, qkv_bias=True, tie_embeddings=True,
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    args = ap.parse_args()

    cfg = config_100m()
    print(f"[lm_train] {cfg.name}: {cfg.param_count()/1e6:.1f}M params")
    params, _ = unzip(M.init_lm(cfg, jax.random.key(0)))

    mesh = auto_mesh((jax.device_count(), 1), ("data", "model"))
    rules = rules_for(cfg, "train", mesh)
    opt = adamw(cosine_schedule(3e-4, args.steps, warmup_steps=20))
    opt_state = opt.init(params)
    knobs = M.PerfKnobs(q_chunk=min(256, args.seq), k_chunk=min(256, args.seq))
    step = jax.jit(build_train_step(cfg, opt, knobs, mesh, rules))

    data = token_batches(args.batch, args.seq, cfg.vocab, seed=7)
    t0, first_loss = time.time(), None
    with jax.set_mesh(mesh):
        for i, (tok, lab) in enumerate(data):
            if i >= args.steps:
                break
            params, opt_state, metrics = step(
                params, opt_state, jnp.int32(i),
                {"tokens": jnp.asarray(tok), "labels": jnp.asarray(lab)},
            )
            loss = float(metrics["loss"])
            first_loss = first_loss or loss
            if (i + 1) % 25 == 0:
                tps = args.batch * args.seq * (i + 1) / (time.time() - t0)
                print(f"step {i+1:4d}  loss {loss:.4f}  ({tps:,.0f} tok/s)")
    import math

    print(f"[lm_train] loss {first_loss:.3f} → {loss:.3f} "
          f"(uniform would be {math.log(cfg.vocab):.3f}); "
          f"{'LEARNED' if loss < first_loss - 0.5 else 'check hyperparams'}")


if __name__ == "__main__":
    main()
