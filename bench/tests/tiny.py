"""A checkout root holding a tiny cell, for running the harness on the CPU.

The configuration keeps qwen2's layer equations (QKV bias, tied
embeddings) at a width the CPU interprets in seconds, with a pairing
rounding at which its 64-wide rows pair, and enough layers and vocabulary
that greedy tokens have close runners-up, so the fp8 control shows.
"""
from __future__ import annotations

import json
import math
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

CONFIG = {
    "name": "tiny", "program_config": "qwen2-1.5b",
    "source": "https://huggingface.co/Qwen/Qwen2-1.5B/blob/main/config.json",
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 4096, "rope_theta": 10000.0, "rms_norm_eps": 1e-06,
    "hidden_act": "silu", "attention_bias": True, "tie_word_embeddings": True,
    "torch_dtype": "bfloat16", "embedding_multiplier": math.sqrt(64),
    "attention_multiplier": 0.25, "residual_multiplier": 1.0, "logits_scaling": 1.0,
    "reduced": [], "assumed": {},
    "deployment": {"gemm": "pallas_paired", "attn": "pallas_fused", "pair_rounding": 0.3,
                   "pair_block_n": 0, "q_chunk": 8, "k_chunk": 8},
}
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
    repository's metric readers, and the tiny cell's data files."""
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
    shutil.copytree(REPO / "bench" / "metrics", root / "bench" / "metrics", dirs_exist_ok=True)
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(config))
    (root / "bench" / "traffic" / "tiny.json").write_text(json.dumps(traffic))
    (root / "bench" / "cells" / "tiny.decode.json").write_text(json.dumps(cell))
    return root
