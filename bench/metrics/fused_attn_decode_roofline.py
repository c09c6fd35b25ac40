"""Share of its roofline the fused decode-attention kernel reaches
(``fused_attn_decode`` events of the device trace): the least time of every
call in the traced window over the device time of those events.

One call per layer per decode step: attention over the live positions of
the active slots (the keys each attends, summed), plus the paired
out-projection with the fused residual, at the out-projection's pair and
residual counts of that layer: the architecture's ``attention_work`` and
``gemms`` (``run.reference``) with ``bench.work.fused_out_projection``.
Returns nothing when the trace holds a different number of such events
than the window made calls.
"""
import sys

from bench import work
from bench.peaks import least_time_s, peaks_for

KERNEL = "fused_attn_decode"


def read(run):
    if run.trace is None or run.config["deployment"]["attn"] != "pallas_fused":
        return None
    cfg, ref = run.config, run.reference
    L = cfg["num_hidden_layers"]
    K, N, _ = ref.gemms(cfg)["wo"]
    peaks = peaks_for(run.device_kind)
    P, R = run.pairs.get("wo", ([0] * L, [K] * L))
    least = 0.0
    for st in run.steps:
        attn = ref.attention_work(cfg, st.context, run.batch_slots)
        for p, r in zip(P, R, strict=True):
            w = work.fused_out_projection(attn, run.batch_slots, K, N, int(p), int(r))
            least += least_time_s(w.flops, w.bytes, peaks)
    count = len(run.steps) * L
    events, seconds = run.trace.op_seconds(KERNEL)
    if events != count or seconds <= 0:
        print(f"[bench] {KERNEL}: {events} trace events for {count} calls; no reading",
              file=sys.stderr)
        return None
    return 100.0 * least / seconds
