"""Sharding rules: divisibility guards, mesh-axis dedup, rule tables."""
import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.parallel.rules import rules_for
from repro.parallel.sharding import Rules, auto_mesh, spec_for_axes


def _mesh2():
    n = jax.device_count()
    return auto_mesh((1, n), ("data", "model"))


RULES = Rules({"batch": ("data",), "ff": "model", "vocab": "model",
               "q_heads": "model", "embed": None})


def test_spec_basic():
    mesh = _mesh2()
    spec = spec_for_axes(("embed", "ff"), mesh=mesh, rules=RULES)
    assert spec == P(None, "model")


@pytest.mark.skipif(jax.device_count() < 2, reason="needs a >1-way mesh axis")
def test_divisibility_guard_replicates():
    mesh = _mesh2()
    n = mesh.shape["model"]
    # dim not divisible by the model axis → replicated
    spec = spec_for_axes(("q_heads",), mesh=mesh, rules=RULES, dim_sizes=(n + 1,))
    assert spec == P(None)
    spec2 = spec_for_axes(("q_heads",), mesh=mesh, rules=RULES, dim_sizes=(n * 3,))
    assert spec2 == P("model")


def test_divisibility_guard_unit_axis():
    """On a size-1 axis everything divides — spec keeps the mapping."""
    mesh = _mesh2()
    spec = spec_for_axes(("q_heads",), mesh=mesh, rules=RULES,
                         dim_sizes=(mesh.shape["model"] * 3,))
    assert spec == P("model")


def test_mesh_axis_used_once():
    """Two logical axes mapping to `model`: priority order wins, later → None."""
    mesh = _mesh2()
    spec = spec_for_axes(("vocab", "ff"), mesh=mesh, rules=RULES)
    assert list(spec).count("model") == 1
    # "vocab" has priority over... both map to model; exactly one survives
    assert spec[0] == "model" or spec[1] == "model"


def test_rules_for_all_archs_and_modes():
    mesh = _mesh2()
    for arch in ("qwen2-1.5b", "deepseek-v2-lite-16b", "mamba2-2.7b", "mistral-large-123b"):
        cfg = get_config(arch)
        for mode in ("train", "prefill", "decode"):
            r = rules_for(cfg, mode, mesh)
            assert r.mesh_axes("layers") is None, "scan dim never shards"
            assert r.mesh_axes("batch") == ("data",)
    big = rules_for(get_config("mistral-large-123b"), "train", mesh)
    assert big.mesh_axes("embed") == "data", "123B trains with FSDP"
    small = rules_for(get_config("qwen2-1.5b"), "train", mesh)
    assert small.mesh_axes("embed") is None


def test_constrain_noop_outside_context():
    import jax.numpy as jnp

    from repro.parallel.sharding import constrain

    x = jnp.ones((4, 4))
    y = constrain(x, "batch", None)  # no mesh/rules active → identity
    assert (y == x).all()


class _FakeMesh:
    """spec_for_axes only reads mesh.shape — enough to exercise multi-way
    guards in a single-device test process."""

    def __init__(self, shape: dict):
        self.shape = shape
        self.axis_names = tuple(shape)


MESH24 = _FakeMesh({"data": 2, "model": 4})


def test_priority_order_contention_multiway():
    """Both "vocab" and "ff" want `model`; "vocab" is earlier in PRIORITY, so
    it wins regardless of dim order and the loser replicates."""
    spec = spec_for_axes(("ff", "vocab"), mesh=MESH24, rules=RULES)
    assert spec == P(None, "model")
    spec = spec_for_axes(("vocab", "ff"), mesh=MESH24, rules=RULES)
    assert spec == P("model", None)


def test_divisibility_fallback_multiway():
    spec = spec_for_axes(
        ("ff", "q_heads"), mesh=MESH24, rules=RULES, dim_sizes=(6, 8)
    )
    # ff=6 doesn't divide the 4-way model axis; q_heads=8 then claims it
    assert spec == P(None, "model")


def test_multi_axis_tuple_partial_overlap_drops_whole_candidate():
    """A rule mapping to ("data", "model") with `model` already claimed must
    drop the *whole* tuple (no partial sharding) — and say why."""
    rules = Rules({"ff": "model", "batch": ("data", "model")})
    reasons: list[tuple[str, str]] = []
    spec = spec_for_axes(
        ("batch", "ff"), mesh=MESH24, rules=rules,
        explain=lambda axis, why: reasons.append((axis, why)),
    )
    assert spec == P(None, "model")  # ff (higher priority) holds model
    assert reasons and reasons[0][0] == "batch"
    assert "model" in reasons[0][1]


def test_explain_hook_reports_divisibility():
    reasons = []
    spec_for_axes(
        ("q_heads",), mesh=MESH24, rules=RULES, dim_sizes=(6,),
        explain=lambda axis, why: reasons.append((axis, why)),
    )
    assert reasons == [(
        "q_heads", "dim 6 not divisible by mesh axes ['model'] (size 4)"
    )]


def test_record_spec_fallbacks_collects_and_counts():
    from repro.parallel.sharding import record_spec_fallbacks

    with record_spec_fallbacks() as fb:
        spec_for_axes(("q_heads",), mesh=MESH24, rules=RULES, dim_sizes=(6,))
        spec_for_axes(("q_heads",), mesh=MESH24, rules=RULES, dim_sizes=(6,))
        spec_for_axes(("ff", "vocab"), mesh=MESH24, rules=RULES)
    assert len(fb) == 2  # deduped by (axis, reason), counted
    (ax0, why0), n0 = next(iter(fb.items())), fb[next(iter(fb))]
    assert ax0[0] == "q_heads" and n0 == 2
    # outside the context nothing records
    spec_for_axes(("q_heads",), mesh=MESH24, rules=RULES, dim_sizes=(6,))
    assert sum(fb.values()) == 3


def test_pairing_meta_axis_replicates_by_rule():
    """The base tables map "pairing_meta" to None — replicated lanes — for
    every arch × mode; placement beside the weight shard comes only from
    paired_shardings_for."""
    for mode in ("train", "prefill", "decode"):
        r = rules_for(get_config("qwen2-1.5b"), mode, MESH24)
        assert r.mesh_axes("pairing_meta") is None


class TestPairingMetaSpec:
    """_pairing_meta_spec derives metadata placement from the *weight's*
    resolved spec — never a fresh rule resolution."""

    def _spec(self, *entries):
        return P(*entries)

    def test_column_sharded_blocked_rides_with_weight(self):
        from repro.parallel.sharding import _pairing_meta_spec

        # wq (L, K, H, hd) sharded on its heads dim; 8 blocks, 4 shards
        got = _pairing_meta_spec(
            "wq", ("layers", "embed", "q_heads", "head_dim"),
            self._spec(None, None, "model", None),
            (2, 16, 4, 2), (2, 8, 5), MESH24,
        )
        assert got == P(None, "model", None)

    def test_row_sharded_weight_metadata_replicates(self):
        from repro.parallel.sharding import _pairing_meta_spec

        got = _pairing_meta_spec(
            "wo", ("layers", "q_heads", "head_dim", "embed"),
            self._spec(None, "model", None, None),
            (2, 4, 2, 16), (2, 16, 5), MESH24,
        )
        assert got == P(None, None, None)

    def test_block_misalignment_replicates(self):
        from repro.parallel.sharding import _pairing_meta_spec

        # 6 blocks over a 4-way shard: boundaries don't align → replicate
        got = _pairing_meta_spec(
            "wq", ("layers", "embed", "ff"),
            self._spec(None, None, "model"),
            (2, 16, 12), (2, 6, 5), MESH24,
        )
        assert got == P(None, None, None)

    def test_structured_metadata_replicates(self):
        from repro.parallel.sharding import _pairing_meta_spec

        got = _pairing_meta_spec(
            "wq", ("layers", "embed", "ff"),
            self._spec(None, None, "model"),
            (2, 16, 8), (2, 7), MESH24,
        )
        assert got == P(None, None)

    def test_expert_axis_copies_weight_spec(self):
        from repro.parallel.sharding import _pairing_meta_spec

        got = _pairing_meta_spec(
            "w_up", ("layers", "experts", "embed", "expert_ff"),
            self._spec(None, "model", None, None),
            (2, 4, 8, 8), (2, 4, 8, 5), MESH24,
        )
        assert got == P(None, "model", None, None)


def test_paired_shardings_for_places_metadata_with_weight():
    """End to end on a real (1, n) mesh: the `_pairing` sibling dict gets
    NamedShardings whose block axis copies the weight's resolved spec."""
    import numpy as np

    from repro.core.transform import pair_params
    from repro.models.param import pairing_axes
    from repro.parallel.sharding import paired_shardings_for

    mesh = _mesh2()
    rng = np.random.default_rng(0)
    wq = rng.normal(size=(2, 16, 8)).astype(np.float32)
    tree = {"segments": [{"attn": {"wq": wq}}]}
    pm, _ = pair_params(
        tree, 0.05, mode="per_column", leaves=(("attn", "wq"),)
    )
    axes = {"segments": [{"attn": {"wq": ("layers", "embed", "q_heads")}}]}
    paxes = pairing_axes(pm, axes)
    rules = Rules({"q_heads": "model", "embed": None, "pairing_meta": None})
    sh = paired_shardings_for(paxes, mesh, rules, pm)
    seg = sh["segments"][0]["attn"]
    assert seg["wq"].spec == P(None, None, "model")
    meta = seg["wq_pairing"]
    assert set(meta) == {"I", "J", "resid", "pair_mask", "resid_mask"}
    for leaf in meta.values():
        # 8 blocks divide the model axis → block dim rides with the weight
        assert leaf.spec == P(None, "model", None)
