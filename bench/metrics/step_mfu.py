"""Model FLOP/s utilization of the whole serving step: the matmul work a
dense model needs for every position processed in the window (prefilled,
absorbed and generated), over the window and the chips' bf16 peak.

Pairing changes how the work is done, not how much: a paired step counts
the dense operations, so pairing shows as a faster step, never as less
work.  Attention counts the live keys only, and the output head counts
once per decode position and once per prefill.  The counts are the
architecture's own (``run.reference``, ``bench/references/``).
"""
from bench.peaks import peaks_for


def read(run):
    cfg, ref = run.config, run.reference
    L = cfg["num_hidden_layers"]
    lin = ref.flops_per_position(cfg)
    head = ref.head_flops(cfg)
    flops = 0.0
    for st in run.steps:
        flops += st.active * (lin + head) + L * ref.attention_work(cfg, st.context, st.active).flops
    for a in run.admits:
        c = a.tokens
        flops += c * lin + head + L * ref.attention_work(cfg, c * (c + 1) // 2, c).flops
    peak = peaks_for(run.device_kind)["bf16_flops"] * run.n_chips
    return 100.0 * flops / ((run.t1 - run.t0) * peak)
