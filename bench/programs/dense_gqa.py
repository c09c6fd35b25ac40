"""Program adapter of the dense GQA decoder (``repro.models.lm``, family
``dense``): the configuration file's sizes onto a ``ModelConfig``, seeded
weights in the program's tree, and the pair counts of the served tree."""
from __future__ import annotations


def program_config(cfg: dict):
    """The program's ModelConfig for a configuration file, which states every
    size; raises where the file asks for equations the program cannot run."""
    import dataclasses
    import math

    from repro.configs import get_config

    base = get_config(cfg["program_config"])
    pcfg = dataclasses.replace(
        base,
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"], n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        rope_theta=cfg["rope_theta"], qkv_bias=cfg["attention_bias"],
        tie_embeddings=cfg["tie_word_embeddings"], dtype=cfg["torch_dtype"],
    )
    fixed = {
        "head_dim": pcfg.head_dim,
        "rms_norm_eps": 1e-6,
        "hidden_act": pcfg.act,
        "embedding_multiplier": math.sqrt(pcfg.d_model) if pcfg.tie_embeddings else 1.0,
        "attention_multiplier": 1.0 / math.sqrt(pcfg.head_dim),
        "residual_multiplier": 1.0,
        "logits_scaling": 1.0,
    }
    for key, want in fixed.items():
        got = cfg[key]
        same = got == want if isinstance(want, str) else math.isclose(got, want, rel_tol=1e-9)
        if not same:
            raise ValueError(f"{cfg['name']}: {key}={got!r}, the program runs {want!r}")
    if pcfg.family != "dense" or pcfg.mla or pcfg.moe or pcfg.qk_norm or pcfg.sliding_window:
        raise ValueError(f"{cfg['name']}: only the dense GQA decoder is supported")
    return pcfg


def params(pcfg, cfg: dict, seed: int):
    """Seeded weights in the program's tree."""
    import jax

    from bench import weights
    from repro.models import lm as M
    from repro.models.param import unzip

    shapes = jax.eval_shape(lambda: unzip(M.init_lm(pcfg, jax.random.key(0)))[0])
    return weights.make_tree(seed, shapes, cfg["torch_dtype"])


def pair_counts(params) -> dict:
    """Per-layer pair and residual lane counts of each paired weight."""
    import numpy as np

    seg = params["segments"][0]
    out = {}
    for sub in ("attn", "mlp"):
        for k, meta in seg.get(sub, {}).items():
            if k.endswith("_pairing"):
                out[k[:-len("_pairing")]] = (
                    np.asarray(meta["pair_mask"]).sum(axis=1).astype(int),
                    np.asarray(meta["resid_mask"]).sum(axis=1).astype(int),
                )
    return out
