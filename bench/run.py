#!/usr/bin/env python3
"""One run of one benchmark cell, on the chip it is started on.

    python3 bench/run.py --workload qwen2-1.5b.decode --seed 7 --seconds 30 --trace 0

The cell (``BENCHMARK.json`` ``workloads``) names a configuration and a
traffic mix; ``bench/registry.py`` finds their files, and the program
adapter (``bench/programs/``) and plain reference (``bench/references/``)
of the architecture the configuration file names.  The run:

1. exits non-zero, printing no result, without a TPU or with fewer chips
   than the cell asks for;
2. keeps freed host memory in glibc's heap (``keep_host_pages``) and turns
   on JAX's persistent compilation cache inside the checkout;
3. makes the weights on the device from ``--seed`` (``bench/weights.py``),
   pairs them and builds one ``ServeEngine`` (the program's own set-up);
4. drives the engine with the closed-loop client (``bench/driver.py``)
   until as many requests have completed as there are slots, which warms
   every shape;
5. measures ``--seconds`` of wall clock (with ``--trace 1``, at most
   ``TRACE_SECONDS`` of it, under the profiler);
6. reads the peak device memory, frees the program, and holds a sample of
   the finished requests to the plain reference;
7. prints the numbers compared beside their limits on stderr, then one JSON
   line on stdout: ``correct``, ``attempted``, ``failed``, ``metrics``,
   ``device`` (and ``breakdown`` with ``--trace 1``), and ``check`` last.

``setup_s`` runs from the start of this process until the window opens.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import ctypes  # noqa: E402
import ctypes.util  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

#: longest stretch a traced run measures: the trace of every device op of a
#: longer window is too large to write and to read back within a run
TRACE_SECONDS = 5.0
#: where traces and other scratch output go (gitignored)
SCRATCH = ROOT / ".cache" / "bench"


#: glibc ``mallopt`` parameters
M_TRIM_THRESHOLD, M_MMAP_MAX = -1, -4


def keep_host_pages() -> bool:
    """Have glibc serve this process's large allocations from its heap and
    keep what is freed there.

    A decode step copies its logits (64 x 151,936 float32, 38.9 MB, above
    glibc's 32 MB ceiling for heap chunks) into a new host buffer.  Left to
    the default, each step maps fresh pages and faults them in, at a cost
    that turns on how many huge pages the kernel grants the mapping; the
    copy's time on a v5e host differs from one process to the next (5-16 ms
    a step) and the end-to-end metrics with it.  Kept in the heap, each
    step reuses resident pages.  Returns False where the C library has no
    ``mallopt``."""
    try:
        libc = ctypes.CDLL(ctypes.util.find_library("c"))
        return (libc.mallopt(M_MMAP_MAX, 0) == 1
                and libc.mallopt(M_TRIM_THRESHOLD, 2**31 - 1) == 1)
    except (OSError, AttributeError):
        return False


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def check_devices(chips: int, require_tpu: bool):
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise SystemExit(f"bench: needs a TPU, JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def program_config(cfg: dict, root: Path = ROOT):
    """The program's ModelConfig for a configuration file, from the adapter
    of the architecture the file names."""
    from bench import registry

    return registry.program(cfg, root).program_config(cfg)


def build_params(pcfg, cfg: dict, seed: int, root: Path = ROOT):
    """Seeded weights in the program's tree, after checking that the tree
    holds exactly the leaves the architecture's reference draws."""
    from bench import registry

    params = registry.program(cfg, root).params(pcfg, cfg, seed)
    check_layout(params, registry.reference(cfg, root).layout(cfg))
    return params


def check_layout(params, want: dict) -> None:
    """Raises unless the program's tree holds exactly the leaves of ``want``
    (path -> shape)."""
    import jax

    from bench import weights

    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    got = {weights.path_str(p): tuple(x.shape) for p, x in flat}
    if got != want:
        raise ValueError(f"program parameters {got} differ from the reference layout {want}")


def sample_for_check(completed: list, seed: int, n: int) -> list:
    """The longest finished request and ``n - 1`` others drawn from the seed."""
    import numpy as np

    if not completed:
        return []
    order = sorted(completed, key=lambda s: (len(s.tokens), len(s.req.prompt)), reverse=True)
    rest = order[1:]
    drawn = np.random.default_rng(seed).permutation(len(rest))[:n - 1]
    return [order[0]] + [rest[int(i)] for i in sorted(drawn)]


def cycle_summary(steps: list) -> str:
    """The window's decode cycles (step start to next step start) on the
    host clock: where a slow run lost its time, spread or stalls."""
    import numpy as np

    if len(steps) < 2:
        return "cycles: too few steps"
    cyc = np.diff([s.start for s in steps]) * 1e3
    step = np.array([s.end - s.start for s in steps]) * 1e3
    med = float(np.median(cyc))
    return (f"cycles: {len(cyc)}, median {med:.2f} ms, p99 {np.percentile(cyc, 99):.2f} ms, "
            f"max {cyc.max():.2f} ms, over twice the median {int((cyc > 2 * med).sum())}; "
            f"step call median {np.median(step):.2f} ms, max {step.max():.2f} ms")


def judge(max_gap, completed: list, failed: list, limit: float) -> bool:
    """The run is correct: requests completed, none malformed, and the widest
    logit gap of the judged picks within the limit."""
    return bool(completed) and not failed and max_gap is not None and max_gap <= limit


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             root: Path = ROOT, require_tpu: bool = True, fault=None,
             control: bool = False) -> dict:
    """One run; returns the result line as a dict.  ``fault`` (tests only)
    is called with the engine before the warm phase, to break it.  With
    ``control`` the fp8 reference's picks are judged in the program's
    place (``bench/control.py``)."""
    from bench import registry

    wl = registry.workload(workload, root)
    cfg = registry.config(wl["config"], root)
    mix = registry.traffic(wl["traffic"], root)
    cell = registry.cell(workload, root)
    program, reference = registry.program(cfg, root), registry.reference(cfg, root)
    devs = check_devices(wl["chips"], require_tpu)

    import jax
    import numpy as np

    from bench import driver, traffic
    from repro.models import lm as M
    from repro.serving.engine import ServeEngine

    dev = devs[0]
    log(f"device {dev.platform} {dev.device_kind} x{len(devs)}; cell {workload}, seed {seed}")
    traffic.validate(mix, cell["max_seq"])
    pcfg = program_config(cfg, root)
    dep = cfg["deployment"]

    t = time.perf_counter()
    params = build_params(pcfg, cfg, seed, root)
    jax.block_until_ready(params)
    log(f"weights made in {time.perf_counter() - t:.1f}s")
    knobs = M.PerfKnobs(q_chunk=dep["q_chunk"], k_chunk=dep["k_chunk"], remat="none",
                        gemm=dep["gemm"], attn=dep["attn"],
                        pair_rounding=dep["pair_rounding"], pair_block_n=dep["pair_block_n"])
    t = time.perf_counter()
    eng = ServeEngine(pcfg, params, max_seq=cell["max_seq"], batch_size=cell["batch_slots"],
                      knobs=knobs)
    log(f"engine built (pairing included) in {time.perf_counter() - t:.1f}s")
    pairs = program.pair_counts(eng.params)
    if fault is not None:
        fault(eng)

    clients = traffic.clients(mix, cfg["vocab_size"], seed, cell["batch_slots"])
    loop = driver.ClosedLoop(eng, clients, mix["prefill_chunk"], cfg["vocab_size"],
                             annotate=trace)
    t = time.perf_counter()
    loop.warm()
    log(f"warm phase: {len(loop.steps)} steps, {len(loop.served)} requests sent, "
        f"{time.perf_counter() - t:.1f}s")

    trace_dir = SCRATCH / "trace" / workload
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=options)
        seconds = min(seconds, TRACE_SECONDS)
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append(secs)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    # no collector pass inside the window: what set-up left is frozen, and
    # the window's own garbage waits until it has closed
    gc.collect()
    gc.freeze()
    gc.disable()
    setup_s = time.perf_counter() - T_START
    t0, t1 = loop.window(seconds)
    gc.enable()
    gc.unfreeze()
    n_compiles = len(compiles)
    if trace:
        jax.profiler.stop_trace()
    log(f"window: {t1 - t0:.3f}s, {n_compiles} compiles in it, setup {setup_s:.1f}s")

    mem = [d.memory_stats() or {} for d in devs]
    peak = max(int(m.get("peak_bytes_in_use", 0)) for m in mem)

    steps = [s for s in loop.steps if driver.in_window(s.end, t0, t1)]
    log(cycle_summary(steps))
    admits = [a for a in loop.admits if driver.in_window(a.end, t0, t1)]
    completed = [s for s in loop.served if driver.in_window(s.done, t0, t1)]
    failed = [s for s in completed if s.bad_token or len(s.tokens) != s.req.max_new]
    run = types.SimpleNamespace(
        workload=workload, cell=cell, config=cfg, mix=mix, setup_s=setup_s,
        t0=t0, t1=t1, steps=steps, admits=admits, served=loop.served,
        completed=completed, pairs=pairs, device_kind=dev.device_kind,
        n_chips=len(devs), batch_slots=cell["batch_slots"], trace=None,
        reference=reference)

    # free the program before the reference runs
    sample = sample_for_check(completed, seed, cell["check_requests"])
    start = mix["prefill_chunk"] - 1
    sequences = [(s.req.prompt, np.asarray(s.tokens, np.int32), start,
                  np.asarray(s.picks, np.int32)) for s in sample]
    del eng, loop, params
    gc.collect()

    result = {"correct": False, "attempted": len(completed), "failed": len(failed)}
    kind = "per_layer" if trace else "end_to_end"
    if trace:
        from bench import trace_reduce

        run.trace = trace_reduce.summarize(trace_reduce.find_xplane(trace_dir), len(devs))
    values = {}
    for m in registry.metrics(workload, kind, root):
        v = registry.reader(m["name"], root)(run)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}
    result["metrics"] = values
    result["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                        "count": len(devs), "memory_peak_bytes": peak}
    if trace:
        result["device"]["busy_s"] = run.trace.busy_s
        result["device"]["window_s"] = run.trace.window_s
        result["breakdown"] = run.trace.breakdown()

    t = time.perf_counter()
    ref = (reference.served_gaps(cfg, seed, dep["pair_rounding"], sequences, control=control,
                                 shape=(cell["check_requests"], cell["max_seq"]))
           if sequences else {"gaps": [], "control_gaps": []})
    served = np.concatenate([x[1] for x in sequences]) if sequences else np.zeros(0)
    judged = sum(len(x[3]) for x in sequences)
    log(f"reference over {len(sequences)} requests: {judged} positions judged, "
        f"{len(served)} served tokens ({len(np.unique(served))} distinct), in "
        f"{time.perf_counter() - t:.1f}s " + json.dumps(ref.get("seconds", {})))
    def widest(key):
        gaps = np.concatenate(ref[key]) if sequences else np.zeros(0)
        return float(gaps.max()) if len(gaps) else None

    limit = cell["limits"]["max_logit_gap"]
    # the control is the fp8 reference put in the program's place: its picks
    # are the ones judged, and the program's own reading stands beside them
    max_gap = widest("control_gaps" if control else "gaps")
    check = {
        "max_logit_gap": {"value": max_gap, "limit": limit},
        "failed_requests": {"value": len(failed), "limit": 0},
        "completed_requests": {"value": len(completed), "limit_at_least": 1},
    }
    if control:
        check["program_max_logit_gap"] = {"value": widest("gaps"), "limit": limit}
    result["correct"] = judge(max_gap, completed, failed, limit)
    result["check"] = check
    for name, c in check.items():
        log(f"check {name} {c['value']!r} limit {c.get('limit', c.get('limit_at_least'))!r}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # the TPU runtime logs inside the checkout, not to a fixed path in /tmp
    os.environ.setdefault("TPU_LOG_DIR", str(SCRATCH / "tpu_logs"))
    log(f"host pages kept: {keep_host_pages()}")
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    # every program, the eager ops' too, goes to the cache: a later run
    # compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    log(f"compile cache {enable_compile_cache()}")
    result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
