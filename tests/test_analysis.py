"""The static-analysis pass: walker semantics, every rule family, the report.

The walker tests pin down the per-call-site/per-eqn-dedup semantics that the
historical ``benchmarks.common._walk_eqns`` got wrong (a sub-jaxpr referenced
from two params of ONE eqn was walked twice, inflating every count).  The
rule tests feed each family a deliberately broken input — a non-permutation
pairing, a mismatched layer stack, an over-budget tile, a while loop that
copies pairing metadata — and require the error finding to fire.
"""
from __future__ import annotations

import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import (
    RULE_REGISTRY,
    AnalysisReport,
    Finding,
    RuleContext,
    count_primitives,
    count_shape_adds,
    pallas_calls_by_scan,
    run_rules,
)
from repro.core.pairing import BlockedPairing, StructuredPairing

# ---------------------------------------------------------------------------
# walker semantics
# ---------------------------------------------------------------------------


def _fake_eqn(primitive_name: str, params: dict):
    """Duck-typed eqn: ``.primitive.name``, ``.params``, ``.invars``/``.outvars``."""
    return types.SimpleNamespace(
        primitive=types.SimpleNamespace(name=primitive_name),
        params=params, invars=(), outvars=(),
    )


def _fake_jaxpr(eqns):
    return types.SimpleNamespace(eqns=list(eqns))


def test_shared_subjaxpr_within_one_eqn_walked_once():
    """Regression for the historical double-walk: one eqn carrying the SAME
    sub-jaxpr object under two params counts its eqns once."""
    inner = _fake_jaxpr([_fake_eqn("sin", {})])
    outer = _fake_jaxpr([_fake_eqn("custom_thing", {"fwd": inner, "bwd": inner})])
    assert count_primitives(outer, "sin") == 1


def test_closed_and_raw_jaxpr_dedupe_together():
    """A ClosedJaxpr-like wrapper and its raw ``.jaxpr`` are one computation."""
    raw = _fake_jaxpr([_fake_eqn("sin", {})])
    closed = types.SimpleNamespace(jaxpr=raw)
    outer = _fake_jaxpr([_fake_eqn("call", {"closed": closed, "raw": raw})])
    assert count_primitives(outer, "sin") == 1


def test_distinct_eqns_counted_per_call_site():
    """Two eqns sharing one sub-jaxpr are two call sites — both execute."""
    inner = _fake_jaxpr([_fake_eqn("sin", {})])
    outer = _fake_jaxpr([
        _fake_eqn("call", {"jaxpr": inner}),
        _fake_eqn("call", {"jaxpr": inner}),
    ])
    assert count_primitives(outer, "sin") == 2


def test_jitted_function_called_twice_counts_both_launches():
    """The real-jax shape of the per-call-site rule: ``f(x) + f(x)`` shares
    one traced ClosedJaxpr across two pjit eqns, but runs twice."""

    @jax.jit
    def f(x):
        return jnp.sin(x)

    jaxpr = jax.make_jaxpr(lambda x: f(x) + f(x))(jnp.ones((4,)))
    assert count_primitives(jaxpr, "sin") == 2


def test_walker_descends_into_scan_bodies():
    def body(c, _):
        return jnp.sin(c), jnp.cos(c)

    jaxpr = jax.make_jaxpr(
        lambda x: jax.lax.scan(body, x, None, length=3)
    )(jnp.ones((4,)))
    assert count_primitives(jaxpr, "sin") == 1
    assert count_primitives(jaxpr, "cos") == 1


def test_walker_descends_into_custom_vjp():
    @jax.custom_vjp
    def f(x):
        return jnp.sin(x)

    f.defvjp(lambda x: (jnp.sin(x), x), lambda x, g: (g * jnp.cos(x),))
    jaxpr = jax.make_jaxpr(jax.grad(lambda x: f(x).sum()))(jnp.ones((4,)))
    assert count_primitives(jaxpr, "cos") == 1


def test_count_shape_adds_matches_full_shape_only():
    h = (2, 1, 8)

    def f(a, b, bias):
        y = a + b          # residual-shaped: counts
        y = y + bias       # broadcast from (8,): must not count
        return y + a       # counts

    args = (jnp.ones(h), jnp.ones(h), jnp.ones((8,)))
    assert count_shape_adds(jax.make_jaxpr(f)(*args), h) == 2


def test_pallas_calls_by_scan_attributes_to_innermost_scan():
    inner_kernel = _fake_jaxpr([_fake_eqn("pallas_call", {})])
    scan_eqn = _fake_eqn("scan", {"jaxpr": inner_kernel, "length": 5})
    top = _fake_jaxpr([scan_eqn, _fake_eqn("pallas_call", {})])
    total, per_scan = pallas_calls_by_scan(top)
    assert total == 2
    (rec,) = per_scan.values()
    assert rec == {"per_trip": 1, "length": 5}


# ---------------------------------------------------------------------------
# schedule rules
# ---------------------------------------------------------------------------


def _run(ctx, *rule_ids):
    return run_rules(ctx, rule_ids=rule_ids)


def test_no_standalone_pool_fires_on_fused_expectation():
    jaxpr = jax.make_jaxpr(
        lambda x: jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID"
        )
    )(jnp.ones((1, 4, 4, 1)))
    bad = _run(
        RuleContext(target="t", jaxpr=jaxpr, expect={"fused_pool": True}),
        "schedule/no-standalone-pool",
    )
    assert bad.exit_code == 1
    assert bad.errors()[0].measured == 1
    ok = _run(
        RuleContext(target="t", jaxpr=jaxpr, expect={}),
        "schedule/no-standalone-pool",
    )
    assert ok.exit_code == 0
    assert ok.measured("schedule/no-standalone-pool") == 1


def test_writebacks_per_program_gate():
    top = _fake_jaxpr([_fake_eqn("pallas_call", {}) for _ in range(3)])
    bad = _run(
        RuleContext(target="t", jaxpr=top, expect={"pallas_calls": 2}),
        "schedule/writebacks-per-program",
    )
    assert bad.exit_code == 1 and bad.errors()[0].measured == 3
    ok = _run(
        RuleContext(target="t", jaxpr=top, expect={"pallas_calls": 3}),
        "schedule/writebacks-per-program",
    )
    assert ok.exit_code == 0


def test_writebacks_per_decode_layer_budget():
    kernels = _fake_jaxpr([_fake_eqn("pallas_call", {}) for _ in range(9)])
    top = _fake_jaxpr([_fake_eqn("scan", {"jaxpr": kernels, "length": 2})])
    bad = _run(
        RuleContext(target="t", jaxpr=top, expect={"writebacks_per_layer": 7}),
        "schedule/writebacks-per-decode-layer",
    )
    assert bad.exit_code == 1
    assert bad.errors()[0].measured == 9 and bad.errors()[0].expected == 7
    ok = _run(
        RuleContext(target="t", jaxpr=top, expect={"writebacks_per_layer": 9}),
        "schedule/writebacks-per-decode-layer",
    )
    assert ok.exit_code == 0
    # an expectation with NO scan in the trace is an error, not a silent pass
    no_scan = _run(
        RuleContext(target="t", jaxpr=_fake_jaxpr([]),
                    expect={"writebacks_per_layer": 7}),
        "schedule/writebacks-per-decode-layer",
    )
    assert no_scan.exit_code == 1


def test_standalone_residual_adds_gate():
    h = (2, 1, 8)
    jaxpr = jax.make_jaxpr(lambda a, b: a + b)(jnp.ones(h), jnp.ones(h))
    bad = _run(
        RuleContext(target="t", jaxpr=jaxpr, hidden_shape=h,
                    expect={"residual_adds": 0}),
        "schedule/standalone-residual-adds",
    )
    assert bad.exit_code == 1 and bad.errors()[0].measured == 1


# ---------------------------------------------------------------------------
# dtype rules
# ---------------------------------------------------------------------------


def test_no_f64_flags_wide_outvars():
    aval = types.SimpleNamespace(dtype=np.dtype("float64"), shape=(4,))
    eqn = _fake_eqn("add", {})
    eqn.outvars = (types.SimpleNamespace(aval=aval),)
    bad = _run(RuleContext(target="t", jaxpr=_fake_jaxpr([eqn])), "dtype/no-f64")
    assert bad.exit_code == 1
    ok = _run(
        RuleContext(target="t", jaxpr=jax.make_jaxpr(jnp.sin)(jnp.ones((4,)))),
        "dtype/no-f64",
    )
    assert ok.exit_code == 0


def test_reduce_precision_required_on_bf16_paired_kernels():
    def paired_eqn(kernel_eqns):
        e = _fake_eqn("pallas_call", {
            "jaxpr": _fake_jaxpr(kernel_eqns),
            "name": "paired_matmul",
        })
        aval = types.SimpleNamespace(dtype=jnp.dtype(jnp.bfloat16), shape=(4, 4))
        e.invars = (types.SimpleNamespace(aval=aval),)
        return e

    unpinned = _run(
        RuleContext(target="t", jaxpr=_fake_jaxpr([paired_eqn([])])),
        "dtype/reduce-precision-on-bf16",
    )
    assert unpinned.exit_code == 1
    pinned = _run(
        RuleContext(
            target="t",
            jaxpr=_fake_jaxpr([paired_eqn([_fake_eqn("reduce_precision", {})])]),
        ),
        "dtype/reduce-precision-on-bf16",
    )
    assert pinned.exit_code == 0
    assert pinned.measured("dtype/reduce-precision-on-bf16") == 1


def test_real_bf16_paired_kernel_carries_reduce_precision():
    """The shipped subtractor kernel satisfies its own dtype rule end to end."""
    from repro.kernels.paired_matmul import paired_matmul_pallas

    x = jnp.ones((8, 16), jnp.bfloat16)
    kmat = jnp.ones((4, 8), jnp.bfloat16)
    wres = jnp.ones((8, 8), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(
        lambda x, k, w: paired_matmul_pallas(x, k, w, block_k=16)
    )(x, kmat, wres)
    rep = _run(
        RuleContext(target="t", jaxpr=jaxpr), "dtype/reduce-precision-on-bf16"
    )
    assert rep.exit_code == 0
    assert rep.measured("dtype/reduce-precision-on-bf16") == 1


def test_convert_churn_warns_over_budget():
    def f(x):
        for _ in range(3):
            x = x.astype(jnp.bfloat16).astype(jnp.float32)
        return x

    jaxpr = jax.make_jaxpr(f)(jnp.ones((4,)))
    rep = _run(
        RuleContext(target="t", jaxpr=jaxpr, expect={"max_converts": 2}),
        "dtype/convert-churn",
    )
    assert rep.exit_code == 0  # warning, not error
    assert len(rep.warnings()) == 1 and rep.warnings()[0].measured == 6


# ---------------------------------------------------------------------------
# VMEM rule
# ---------------------------------------------------------------------------


def test_vmem_estimator_double_buffers_inputs():
    from repro.kernels.tuning import estimate_pallas_vmem_bytes

    est = estimate_pallas_vmem_bytes(
        in_blocks=[((8, 4), 4)], out_blocks=[((8, 2), 2)],
        scratch_blocks=[((None, 4), 4)],
    )
    assert est == 2 * 8 * 4 * 4 + 8 * 2 * 2 + 4 * 4


def test_vmem_budget_flags_oversized_blocks():
    from repro.kernels.paired_matmul import dense_matmul_pallas

    x = jnp.ones((1024, 1024), jnp.float32)
    w = jnp.ones((1024, 1024), jnp.float32)

    def over(x, w):
        return dense_matmul_pallas(
            x, w, block_m=1024, block_n=1024, block_k=1024
        )

    def under(x, w):
        return dense_matmul_pallas(x, w, block_m=128, block_n=128, block_k=512)

    bad = _run(
        RuleContext(target="t", jaxpr=jax.make_jaxpr(over)(x, w)),
        "vmem/static-budget",
    )
    assert bad.exit_code == 1
    assert bad.errors()[0].measured > 8 * 1024 * 1024
    ok = _run(
        RuleContext(target="t", jaxpr=jax.make_jaxpr(under)(x, w)),
        "vmem/static-budget",
    )
    assert ok.exit_code == 0


# ---------------------------------------------------------------------------
# pairing-artifact rules
# ---------------------------------------------------------------------------


def _structured(I, J, resid, K, N=2):
    I, J, resid = (np.asarray(a, np.int64) for a in (I, J, resid))
    return StructuredPairing(
        I=I, J=J, Kmat=np.ones((len(I), N)), resid=resid,
        W_res=np.ones((len(resid), N)), shape=(K, N),
    )


def test_valid_permutation_accepts_good_and_flags_bad():
    good = {"conv1": _structured([0, 1], [3, 2], [4, 5], K=6)}
    ok = _run(
        RuleContext(target="t", pairing_artifacts=good),
        "pairing/valid-permutation",
    )
    assert ok.exit_code == 0

    # row 3 appears twice, row 2 never: not a permutation
    bad = {"conv1": _structured([0, 1], [3, 3], [4, 5], K=6)}
    rep = _run(
        RuleContext(target="t", pairing_artifacts=bad),
        "pairing/valid-permutation",
    )
    assert rep.exit_code == 1
    assert "conv1" in rep.errors()[0].location


def test_blocked_pairing_artifacts_validate_through_masks():
    blocks = [
        _structured([0, 1], [3, 2], [4, 5], K=6, N=2),
        _structured([5], [0], [1, 2, 3, 4], K=6, N=2),
    ]
    bp = BlockedPairing(blocks=blocks, block_n=2, shape=(6, 4))
    rep = _run(
        RuleContext(target="t", pairing_artifacts={"conv1": bp}),
        "pairing/valid-permutation", "pairing/padding-consistent",
    )
    assert rep.exit_code == 0
    assert rep.measured("pairing/valid-permutation", location="t") == 2


def test_padding_consistency_flags_nonzero_padded_lanes(monkeypatch):
    rep = _run(
        RuleContext(
            target="t",
            pairing_artifacts={"conv1": BlockedPairing(
                blocks=[_structured([1], [4], [0, 3, 5], K=6)],
                block_n=2, shape=(6, 2),
            )},
        ),
        "pairing/padding-consistent",
    )
    assert rep.exit_code == 0  # the real builder pads correctly

    # hand-corrupt the packed arrays: a padded lane pointing off row 0
    import repro.analysis.rules_pairing as rp

    bad = rp._Artifact(
        location="conv1/block0", K=6,
        I=np.array([1, 2]), J=np.array([4, 2]), resid=np.array([0, 3, 5]),
        pair_mask=np.array([1.0, 0.0]), resid_mask=np.array([1.0, 1.0, 1.0]),
    )
    monkeypatch.setattr(rp, "_all_artifacts", lambda _ctx: [bad])
    rep2 = _run(RuleContext(target="t", pairing_artifacts={}), "pairing/padding-consistent")
    assert rep2.exit_code == 1
    assert "point at row 0" in rep2.errors()[0].message


def _fake_lm_params(L=2, K=8, N=4, *, stack_layers=None, bad_index=False):
    stack_layers = L if stack_layers is None else stack_layers
    P, R = 2, K - 4
    meta = {
        "I": np.zeros((stack_layers, P), np.int32),
        "J": np.ones((stack_layers, P), np.int32),
        "resid": np.tile(np.arange(4, K, dtype=np.int32), (stack_layers, 1)),
        "pair_mask": np.ones((stack_layers, P)),
        "resid_mask": np.ones((stack_layers, R)),
    }
    meta["I"][:, 1] = 2
    meta["J"][:, 1] = 3
    if bad_index:
        meta["J"][:, 0] = K + 3  # out of the weight's contraction range
    return {"segments": [{
        "attn": {"wq": np.zeros((L, K, N)), "wq_pairing": meta},
    }]}


def test_stacked_shapes_accepts_consistent_metadata():
    rep = _run(
        RuleContext(target="t", params=_fake_lm_params()),
        "pairing/stacked-shapes", "pairing/valid-permutation",
    )
    assert rep.exit_code == 0
    assert rep.measured("pairing/stacked-shapes", location="t") == 1


def test_stacked_shapes_flags_layer_mismatch_and_bad_index():
    mismatched = _run(
        RuleContext(target="t", params=_fake_lm_params(L=2, stack_layers=3)),
        "pairing/stacked-shapes",
    )
    assert mismatched.exit_code == 1
    assert "3 layer(s), weight stacks 2" in mismatched.errors()[0].message

    oob = _run(
        RuleContext(target="t", params=_fake_lm_params(bad_index=True)),
        "pairing/stacked-shapes",
    )
    assert oob.exit_code == 1
    assert "outside the weight's K=8" in oob.errors()[0].message


def test_real_paired_lm_params_pass_all_pairing_rules():
    from repro.configs import get_smoke_config
    from repro.core.transform import pair_lm_params
    from repro.models import lm as M
    from repro.models.param import unzip

    cfg = get_smoke_config("qwen2-1.5b")
    params, _ = unzip(M.init_lm(cfg, jax.random.key(0)))
    pm, _ = pair_lm_params(params, 0.05, mode="per_column")
    rep = _run(
        RuleContext(target="t", params=pm),
        "pairing/valid-permutation", "pairing/padding-consistent",
        "pairing/stacked-shapes",
    )
    assert rep.exit_code == 0, [f.as_dict() for f in rep.errors()]


# ---------------------------------------------------------------------------
# HLO rule
# ---------------------------------------------------------------------------

_HLO_CLEAN = """
HloModule decode

%body (p0: (s32[], f32[2,8])) -> (s32[], f32[2,8]) {
  %p0 = (s32[], f32[2,8]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%p0), index=0
  %h = f32[2,8]{1,0} get-tuple-element(%p0), index=1
  %one = s32[] constant(1)
  %i2 = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[2,8]{1,0}) tuple(%i2, %h)
}

%cond (p0: (s32[], f32[2,8])) -> pred[] {
  %p0 = (s32[], f32[2,8]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%p0), index=0
  %n = s32[] constant(4)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (meta: s32[2,4,3], h0: f32[2,8]) -> f32[2,8] {
  %meta = s32[2,4,3]{2,1,0} parameter(0), metadata={op_name="p['segments'][0]['attn']['wq_pairing']['I']"}
  %h0 = f32[2,8]{1,0} parameter(1)
  %z = s32[] constant(0)
  %init = (s32[], f32[2,8]{1,0}) tuple(%z, %h0)
  %w = (s32[], f32[2,8]{1,0}) while(%init), condition=%cond, body=%body
  ROOT %out = f32[2,8]{1,0} get-tuple-element(%w), index=1
}
"""

# same module, but the while body copies a buffer of the pairing-metadata
# type (s32[2,4,3]) every trip — the rule must flag it
_HLO_DIRTY = _HLO_CLEAN.replace(
    "  %one = s32[] constant(1)",
    "  %bad = s32[2,4,3]{2,1,0} copy(%meta)\n  %one = s32[] constant(1)",
)


def test_hlo_rule_clean_loop_passes():
    rep = _run(
        RuleContext(target="t", hlo_text=_HLO_CLEAN),
        "hlo/pairing-resharding-in-loop",
    )
    assert rep.exit_code == 0
    assert rep.measured("hlo/pairing-resharding-in-loop", location="t") == 0


def test_hlo_rule_flags_copy_of_pairing_buffer_in_loop():
    rep = _run(
        RuleContext(target="t", hlo_text=_HLO_DIRTY),
        "hlo/pairing-resharding-in-loop",
    )
    assert rep.exit_code == 1
    err = rep.errors()[0]
    assert err.measured == "copy" and "body" in err.location


def test_hlo_rule_copy_outside_loop_is_fine():
    hlo = _HLO_CLEAN.replace(
        "  %z = s32[] constant(0)",
        "  %c = s32[2,4,3]{2,1,0} copy(%meta)\n  %z = s32[] constant(0)",
    )
    rep = _run(
        RuleContext(target="t", hlo_text=hlo), "hlo/pairing-resharding-in-loop"
    )
    assert rep.exit_code == 0


# ---------------------------------------------------------------------------
# registry / report plumbing
# ---------------------------------------------------------------------------


def test_registry_contains_all_twelve_rules():
    run_rules(RuleContext(target="t"))  # force registration
    assert sorted(RULE_REGISTRY) == [
        "dtype/convert-churn",
        "dtype/no-f64",
        "dtype/reduce-precision-on-bf16",
        "hlo/pairing-resharding-in-loop",
        "pairing/padding-consistent",
        "pairing/stacked-shapes",
        "pairing/valid-permutation",
        "schedule/no-standalone-pool",
        "schedule/standalone-residual-adds",
        "schedule/writebacks-per-decode-layer",
        "schedule/writebacks-per-program",
        "vmem/static-budget",
    ]


def test_unmet_needs_are_recorded_not_dropped():
    rep = run_rules(RuleContext(target="t"))  # context provides nothing
    assert rep.rules_run == []
    assert set(rep.rules_skipped) == set(RULE_REGISTRY)
    assert rep.rules_skipped["hlo/pairing-resharding-in-loop"] == "hlo"
    assert rep.exit_code == 0


def test_unknown_rule_id_is_an_assertion():
    with pytest.raises(AssertionError):
        run_rules(RuleContext(target="t"), rule_ids=["schedule/no-such-rule"])


def test_report_json_round_trip_and_measured_lookup():
    rep = AnalysisReport(
        target="t",
        findings=[
            Finding("r/a", "info", "t", "m", measured=7, expected=7),
            Finding("r/b", "error", "t/x", "boom", measured=9, expected=7),
        ],
        rules_run=["r/a", "r/b"],
        rules_skipped={"r/c": "hlo"},
    )
    assert rep.exit_code == 1
    assert rep.measured("r/a") == 7
    assert rep.measured("r/b", location="t/x") == 9
    with pytest.raises(KeyError):
        rep.measured("r/absent")
    d = json.loads(rep.to_json())
    assert d["errors"] == 1 and d["rules_skipped"] == {"r/c": "hlo"}
    assert d["findings"][1]["severity"] == "error"
    assert any("ERROR r/b" in line for line in rep.summary_lines())


def test_benchmarks_common_reexports_the_analysis_walker():
    from benchmarks import common
    from repro.analysis import jaxpr_walk

    assert common.count_primitives is jaxpr_walk.count_primitives
    assert common.count_shape_adds is jaxpr_walk.count_shape_adds


def test_lenet_fused_target_runs_clean_end_to_end():
    """The CLI's fastest target: every runnable rule fires, none errors, and
    the skipped rules are exactly the facets LeNet doesn't provide."""
    from repro.analysis.targets import build_context

    rep = run_rules(build_context("lenet_fused"))
    assert rep.exit_code == 0, [f.as_dict() for f in rep.errors()]
    assert rep.measured("schedule/writebacks-per-program") == 3
    assert rep.measured("schedule/no-standalone-pool") == 0
    assert set(rep.rules_skipped) == {
        "hlo/pairing-resharding-in-loop",
        "schedule/standalone-residual-adds",
    }
