"""Share of its roofline the paired GEMM kernel reaches (``paired_matmul``
events of the device trace): the least time of every call in the traced
window, over the device time of those events.

A call's work is ``y = (x[I] - x[J]) K + x[R] W_R`` at the call's own
shapes (``bench.work.paired_gemm``; each weight's N and fused residual from
the architecture's ``gemms``, ``run.reference``), with each layer's own
pair and residual counts; padding lanes are not work.  A decode step calls
the kernel for every paired weight of every layer but the out-projection,
which the fused decode-attention kernel applies when ``attn`` is
``pallas_fused``; a prefill calls it for all seven.  Returns nothing when
the trace holds a different number of such events than the window made
calls: then the events are not these calls.
"""
import sys

from bench import work
from bench.peaks import least_time_s, peaks_for

KERNEL = "paired_matmul"


def calls(run, M, names):
    """(least seconds, call count) of one launch of each of ``names`` per layer."""
    gemms = run.reference.gemms(run.config)
    peaks = peaks_for(run.device_kind)
    total, n = 0.0, 0
    for name in names:
        _, N, residual = gemms[name]
        P, R = run.pairs[name]
        for p, r in zip(P, R, strict=True):
            if p == 0:
                continue  # no pair lanes: the kernel is dense_matmul
            w = work.paired_gemm(M, N, int(p), int(r), residual=residual)
            total += least_time_s(w.flops, w.bytes, peaks)
            n += 1
    return total, n


def read(run):
    if run.trace is None or not run.pairs:
        return None
    fused_wo = run.config["deployment"]["attn"] == "pallas_fused"
    names = [n for n in run.pairs if not (fused_wo and n == "wo")]
    step_t, step_n = calls(run, run.batch_slots, names)
    least, count = step_t * len(run.steps), step_n * len(run.steps)
    for a in run.admits:
        t, n = calls(run, a.tokens, list(run.pairs))
        least, count = least + t, count + n
    events, seconds = run.trace.op_seconds(KERNEL)
    if events != count or seconds <= 0:
        print(f"[bench] {KERNEL}: {events} trace events for {count} calls; no reading",
              file=sys.stderr)
        return None
    return 100.0 * least / seconds
