"""A checkout root holding a tiny cell, for running the harness on the CPU.

``tiny_configs/<architecture>.json`` holds each architecture's tiny
configuration.  The dense GQA one keeps qwen2's layer equations (QKV bias,
tied embeddings) at a width the CPU interprets in seconds, with a pairing
rounding at which its 64-wide rows pair, and enough layers and vocabulary
that greedy tokens have close runners-up, so the fp8 control shows.
"""
from __future__ import annotations

import json
import math
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
TINY_CONFIGS = Path(__file__).resolve().parent / "tiny_configs"


def tiny_config(architecture: str) -> dict:
    """The tiny configuration of an architecture."""
    return json.loads((TINY_CONFIGS / f"{architecture}.json").read_text())


def bench_config(pcfg) -> dict:
    """A dense GQA configuration file for a program ``ModelConfig``, with the
    program's own equations (its embedding scale and softmax scale)."""
    return {
        "name": pcfg.name, "architecture": "dense_gqa", "hidden_size": pcfg.d_model,
        "intermediate_size": pcfg.d_ff, "num_hidden_layers": pcfg.n_layers,
        "num_attention_heads": pcfg.n_heads, "num_key_value_heads": pcfg.n_kv_heads,
        "head_dim": pcfg.head_dim, "vocab_size": pcfg.vocab, "rope_theta": pcfg.rope_theta,
        "rms_norm_eps": 1e-6, "attention_bias": pcfg.qkv_bias,
        "tie_word_embeddings": pcfg.tie_embeddings, "torch_dtype": "float32",
        "embedding_multiplier": math.sqrt(pcfg.d_model),
        "attention_multiplier": 1 / math.sqrt(pcfg.head_dim),
        "residual_multiplier": 1.0, "logits_scaling": 1.0,
    }


CONFIG = tiny_config("dense_gqa")
TRAFFIC = {
    "loop": "closed", "prefill_chunk": 4, "block": 8,
    "prompt": {"dist": "lognormal", "median": 8, "sigma": 0.5, "min": 4, "max": 16},
    "output": {"dist": "lognormal", "median": 6, "sigma": 0.5, "min": 2, "max": 12},
}
#: the limit lies between the readings of this size on the CPU: the served
#: bf16 path read at most 0.0014, the fp8 control 0.057 and more
CELL = {"batch_slots": 4, "max_seq": 32, "check_requests": 200,
        "limits": {"max_logit_gap": 0.02}}


def make_root(tmp: Path, *, config=CONFIG, traffic=TRAFFIC, cell=CELL) -> Path:
    """A checkout root with BENCHMARK.json naming one tiny cell, the
    repository's metric readers, program adapters and references, and the
    tiny cell's data files."""
    root = Path(tmp)
    root.mkdir(parents=True, exist_ok=True)
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny", "source": config["source"],
                         "file": "bench/configs/tiny.json", "reduced": [], "why": "CPU test"}]
    bench["workloads"] = [{"name": "tiny.decode", "config": "tiny", "traffic": "tiny",
                           "chips": 1, "why": "CPU test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    for sub in ("configs", "traffic", "cells"):
        (root / "bench" / sub).mkdir(parents=True, exist_ok=True)
    for sub in ("metrics", "programs", "references"):
        shutil.copytree(REPO / "bench" / sub, root / "bench" / sub, dirs_exist_ok=True,
                        ignore=shutil.ignore_patterns("__pycache__"))
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(config))
    (root / "bench" / "traffic" / "tiny.json").write_text(json.dumps(traffic))
    (root / "bench" / "cells" / "tiny.decode.json").write_text(json.dumps(cell))
    return root
