"""Model FLOP/s utilization of the whole serving step: the matmul work a
dense model needs for every position processed in the window (prefilled,
absorbed and generated), over the window and the chips' bf16 peak.

Pairing changes how the work is done, not how much: a paired step counts
the dense operations, so pairing shows as a faster step, never as less
work.  Attention counts the live keys only, and the output head counts
once per decode position and once per prefill.
"""
from bench import reference, work
from bench.peaks import peaks_for


def read(run):
    s = reference.sizes(run.config)
    L, d, H, KH, hd, f, V = s["L"], s["d"], s["H"], s["KH"], s["hd"], s["f"], s["V"]
    lin = work.dense_flops_per_position(L, d, H, KH, hd, f)
    head = work.head_flops(d, V)
    flops = 0.0
    for st in run.steps:
        flops += st.active * (lin + head) + work.attention_flops(L, H, hd, st.context)
    for a in run.admits:
        c = a.tokens
        flops += c * lin + head + work.attention_flops(L, H, hd, c * (c + 1) // 2)
    peak = peaks_for(run.device_kind)["bf16_flops"] * run.n_chips
    return 100.0 * flops / ((run.t1 - run.t0) * peak)
