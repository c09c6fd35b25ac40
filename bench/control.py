#!/usr/bin/env python3
"""The control of the correctness check, run on the chip.

    python3 bench/control.py --workload qwen2-1.5b.decode --seeds 11,12,13 --seconds 10

For each seed, in one process: one run of the cell (``run.run_cell``) with
the control in the program's place: the reference runs a second time in
fp8 (the precision below the configuration's bfloat16) over the same
prompts and served tokens, and the token it ranks first at each position
goes through the check that decides ``correct``.  A sound benchmark prints
``correct`` false for every seed.  Each JSON line also carries the
program's own widest gap from the same run (the lower reading) beside the
control's (the upper reading) and the cell's limit.  The benchmark's own
runs do not run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parent.parent)]

from bench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", str(run.SCRATCH / "tpu_logs"))
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    enable_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        r = run.run_cell(args.workload, seed, args.seconds, False, control=True)
        c = r["check"]
        print(json.dumps({
            "workload": args.workload, "seed": seed, "correct": r["correct"],
            "program_max_logit_gap": c["program_max_logit_gap"]["value"],
            "control_max_logit_gap": c["max_logit_gap"]["value"],
            "limit": c["max_logit_gap"]["limit"],
            "completed": c["completed_requests"]["value"],
            "metrics": {k: v["value"] for k, v in r["metrics"].items()},
            "memory_peak_bytes": r["device"]["memory_peak_bytes"],
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
