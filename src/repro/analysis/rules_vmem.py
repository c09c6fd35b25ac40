"""Static VMEM-budget rule.

Every ``pallas_call`` in the traced program declares its block specs at trace
time; charging them against :data:`repro.kernels.tuning.VMEM_BUDGET_BYTES`
catches an over-budget tile choice *before* anything runs — on TPU that is
the difference between a compile-time report and a Mosaic OOM mid-serve.
"""
from __future__ import annotations

from repro.analysis.core import Finding, RuleContext, rule
from repro.analysis.jaxpr_walk import walk_eqns
from repro.kernels import tuning


def _block_specs(eqn) -> tuple[list, list, list] | None:
    """(in_blocks, out_blocks, scratch_blocks) of one pallas_call eqn, each a
    list of ``(block_shape, dtype_bytes)`` — None when the eqn carries no
    grid mapping (e.g. a synthetic test jaxpr)."""
    gm = eqn.params.get("grid_mapping")
    if gm is None:
        return None
    mappings = list(getattr(gm, "block_mappings", ()))
    n_in = getattr(gm, "num_inputs", len(mappings))
    blocks = []
    for bm in mappings:
        shape = tuple(getattr(bm, "block_shape", ()))
        sds = getattr(bm, "array_shape_dtype", None)
        itemsize = getattr(getattr(sds, "dtype", None), "itemsize", 4)
        blocks.append((shape, itemsize))
    in_blocks, out_blocks = blocks[:n_in], blocks[n_in:]

    scratch = []
    kernel_jaxpr = eqn.params.get("jaxpr")
    n_scratch = getattr(gm, "num_scratch_operands", 0)
    if kernel_jaxpr is not None and n_scratch:
        for v in kernel_jaxpr.invars[len(mappings):]:
            aval = getattr(v, "aval", None)
            inner = getattr(aval, "inner_aval", aval)  # AbstractMemoryRef
            shape = tuple(getattr(inner, "shape", ()))
            itemsize = getattr(getattr(inner, "dtype", None), "itemsize", 4)
            scratch.append((shape, itemsize))
    return in_blocks, out_blocks, scratch


@rule("vmem/static-budget", needs=("jaxpr",))
def static_budget(ctx: RuleContext):
    """Every pallas_call's block-spec working set must fit the VMEM budget."""
    budget = ctx.expect.get("vmem_budget_bytes", tuning.VMEM_BUDGET_BYTES)
    n_calls = 0
    peak = 0
    for eqn in walk_eqns(ctx.jaxpr):
        if eqn.primitive.name != "pallas_call":
            continue
        specs = _block_specs(eqn)
        if specs is None:
            continue
        n_calls += 1
        est = tuning.estimate_pallas_vmem_bytes(*specs)
        peak = max(peak, est)
        if est > budget:
            name = eqn.params.get("name") or "pallas_call"
            in_blocks = [s for s, _ in specs[0]]
            yield Finding(
                rule="vmem/static-budget",
                severity="error",
                location=f"{ctx.target}/{name}",
                message=f"block specs {in_blocks} budget {est} bytes of VMEM "
                        f"per program — over the {budget}-byte budget",
                measured=est,
                expected=budget,
            )
    yield Finding(
        rule="vmem/static-budget",
        severity="info",
        location=ctx.target,
        message=f"{n_calls} pallas_call(s) checked, peak static working set "
                f"{peak} bytes (budget {budget})",
        measured=peak,
        expected=budget,
    )
