"""Share of its roofline the fused decode-attention kernel reaches
(``fused_attn_decode`` events of the device trace): the least time of every
call in the traced window over the device time of those events.

One call per layer per decode step: attention over the live positions of
the active slots (the keys each attends, summed), plus the paired
out-projection with the fused residual, at the out-projection's pair and
residual counts of that layer (``bench.work.decode_attention``).  Returns
nothing when the trace holds a different number of such events than the
window made calls.
"""
import sys

from bench import reference, work
from bench.peaks import least_time_s, peaks_for

KERNEL = "fused_attn_decode"


def read(run):
    if run.trace is None or run.config["deployment"]["attn"] != "pallas_fused":
        return None
    s = reference.sizes(run.config)
    peaks = peaks_for(run.device_kind)
    P, R = run.pairs.get("wo", ([0] * s["L"], [s["H"] * s["hd"]] * s["L"]))
    least = 0.0
    for st in run.steps:
        for p, r in zip(P, R, strict=True):
            w = work.decode_attention(st.context, run.batch_slots, s["H"], s["KH"], s["hd"],
                                      s["d"], int(p), int(r))
            least += least_time_s(w.flops, w.bytes, peaks)
    count = len(run.steps) * s["L"]
    events, seconds = run.trace.op_seconds(KERNEL)
    if events != count or seconds <= 0:
        print(f"[bench] {KERNEL}: {events} trace events for {count} calls; no reading",
              file=sys.stderr)
        return None
    return 100.0 * least / seconds
