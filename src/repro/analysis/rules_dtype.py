"""Dtype-discipline rules.

Approximate-arithmetic accelerators live or die on numeric drift (cf. the
approximate-multiplier literature): the bf16 subtractor path pins its
rounding semantics with an explicit ``reduce_precision`` in the kernel, f64
anywhere means a silent 2x-width fallback slipped in, and
``convert_element_type`` churn measures how often the schedule bounces
activations between widths.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.analysis.core import Finding, RuleContext, rule
from repro.analysis.jaxpr_walk import count_primitives, walk_eqns

_WIDE_DTYPES = ("float64", "complex128")


def _aval_dtype(v):
    return getattr(getattr(v, "aval", None), "dtype", None)


def _is_low_precision_float(dtype) -> bool:
    try:
        return bool(jnp.issubdtype(dtype, jnp.floating)) and dtype.itemsize < 4
    except TypeError:
        return False


def _pallas_kernel_name(eqn) -> str:
    return str(eqn.params.get("name") or "")


@rule("dtype/no-f64", needs=("jaxpr",))
def no_f64(ctx: RuleContext):
    """No float64/complex128 anywhere in the traced program."""
    hits: list[str] = []
    for eqn in walk_eqns(ctx.jaxpr):
        for v in eqn.outvars:
            dt = _aval_dtype(v)
            if dt is not None and str(dt) in _WIDE_DTYPES:
                hits.append(f"{eqn.primitive.name}:{dt}")
    if hits:
        yield Finding(
            rule="dtype/no-f64",
            severity="error",
            location=ctx.target,
            message=f"{len(hits)} eqn(s) produce 64-bit values "
                    f"(e.g. {hits[0]}) — the inference paths are ≤ 32-bit",
            measured=len(hits),
            expected=0,
        )
    else:
        yield Finding(
            rule="dtype/no-f64",
            severity="info",
            location=ctx.target,
            message="no 64-bit values in the traced program",
            measured=0,
            expected=0,
        )


@rule("dtype/reduce-precision-on-bf16", needs=("jaxpr",))
def reduce_precision_on_bf16(ctx: RuleContext):
    """bf16 subtractor kernels must pin rounding with ``reduce_precision``."""
    checked = 0
    for eqn in walk_eqns(ctx.jaxpr):
        if eqn.primitive.name != "pallas_call":
            continue
        name = _pallas_kernel_name(eqn)
        if "paired" not in name:
            continue  # dense/flash kernels have no subtractor lanes to pin
        low = [str(dt) for dt in map(_aval_dtype, eqn.invars)
               if dt is not None and _is_low_precision_float(dt)]
        if not low:
            continue
        checked += 1
        kernel_jaxpr = eqn.params.get("jaxpr")
        n_rp = count_primitives(kernel_jaxpr, "reduce_precision") if kernel_jaxpr else 0
        if n_rp == 0:
            yield Finding(
                rule="dtype/reduce-precision-on-bf16",
                severity="error",
                location=f"{ctx.target}/{name}",
                message=f"subtractor kernel consumes {sorted(set(low))} inputs "
                        f"but applies no reduce_precision — low-precision "
                        f"rounding semantics are unpinned",
                measured=n_rp,
                expected=">= 1",
            )
    yield Finding(
        rule="dtype/reduce-precision-on-bf16",
        severity="info",
        location=ctx.target,
        message=f"{checked} low-precision subtractor kernel(s) checked",
        measured=checked,
        expected=None,
    )


@rule("dtype/convert-churn", needs=("jaxpr",))
def convert_churn(ctx: RuleContext):
    """``convert_element_type`` churn counter — widening/narrowing bounces."""
    n = count_primitives(ctx.jaxpr, "convert_element_type")
    cap = ctx.expect.get("max_converts")
    if cap is not None and n > cap:
        yield Finding(
            rule="dtype/convert-churn",
            severity="warning",
            location=ctx.target,
            message=f"{n} convert_element_type op(s) exceed the target's "
                    f"budget of {cap} — check for width bouncing",
            measured=n,
            expected=cap,
        )
    else:
        yield Finding(
            rule="dtype/convert-churn",
            severity="info",
            location=ctx.target,
            message=f"{n} convert_element_type op(s) in the traced program",
            measured=n,
            expected=cap,
        )
