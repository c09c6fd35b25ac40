"""The plain dense GQA reference (``bench/references/dense_gqa.py``, with
the shared pair walk of ``bench/reference.py``) against the program's own
forward (``models.lm``) on the CPU, at the smoke sizes of both
configurations, with and without pairs.

Both run in float32 at full matmul precision on the same seeded weights, so
they agree to float32 rounding over the depth; a rolled pair permutation or
a dropped residual connection in the reference misses by orders of
magnitude more.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference, weights
from bench.references import dense_gqa
from bench.tests.tiny import bench_config

#: max |reference - program| / max |program| over all logits: float32 at
#: full precision on both sides, two layers
TOL = 1e-4


def program_logits(pcfg, params, rounding, tokens):
    from repro.core.transform import pair_lm_params
    from repro.kernels.ops import perf_context
    from repro.models import lm as M

    knobs = M.PerfKnobs(q_chunk=8, k_chunk=8, remat="none", gemm="pallas_paired")
    paired, report = pair_lm_params(params, rounding)
    with perf_context(knobs), jax.default_matmul_precision("highest"):
        logits, _, _ = M.lm_forward(pcfg, paired, {"tokens": jnp.asarray(tokens)}, knobs=knobs)
    return np.asarray(logits)[..., :pcfg.vocab], report.total_pairs


@pytest.fixture(scope="module", params=["qwen2-1.5b", "granite-3-2b"])
def smoke(request):
    from repro.configs import get_smoke_config
    from repro.models import lm as M
    from repro.models.param import unzip

    pcfg = dataclasses.replace(get_smoke_config(request.param), dtype="float32")
    cfg = bench_config(pcfg)
    shapes = jax.eval_shape(lambda: unzip(M.init_lm(pcfg, jax.random.key(0)))[0])
    params = weights.make_tree(7, shapes, "float32")
    tokens = np.random.default_rng(0).integers(0, pcfg.vocab, (2, 12)).astype(np.int32)
    return pcfg, cfg, params, tokens


def rel_err(a, b):
    return float(np.abs(a - b).max() / np.abs(b).max())


@pytest.mark.parametrize("rounding", [0.0, 0.05, 0.3])
def test_reference_matches_program_forward(smoke, rounding):
    pcfg, cfg, params, tokens = smoke
    want, pairs = program_logits(pcfg, params, rounding, tokens)
    if rounding == 0.3:
        assert pairs > 0, "the check at 0.3 must go through pair lanes"
    got = dense_gqa.logits(cfg, 7, rounding, tokens)
    assert rel_err(got, want) < TOL


@pytest.mark.parametrize("mutation", ["rolled_permutation", "dropped_residual"])
def test_reference_check_catches_a_broken_forward(smoke, mutation, monkeypatch):
    pcfg, cfg, params, tokens = smoke
    want, _ = program_logits(pcfg, params, 0.3, tokens)
    if mutation == "rolled_permutation":
        pair_rows = reference.pair_rows

        def rolled(W, r):
            I, J, R = pair_rows(W, r)
            return np.roll(I, 1), J, R
        monkeypatch.setattr(reference, "pair_rows", rolled)
    else:
        layer = dense_gqa.make_layer

        def no_skip(cfg, low):
            f = layer(cfg, low)
            return lambda h, w: f(h, w) - h
        monkeypatch.setattr(dense_gqa, "make_layer", no_skip)
    got = dense_gqa.logits(cfg, 7, 0.3, tokens)
    assert rel_err(got, want) > 100 * TOL


@pytest.mark.parametrize("shape", [(64, 32), (96, 40), (128, 256), (256, 64)])
@pytest.mark.parametrize("rounding", [0.02, 0.1, 0.2, 0.4])
def test_pairing_walk_matches_the_program(shape, rounding):
    from repro.core.pairing import pair_rows_structured

    W = np.asarray(jax.random.truncated_normal(jax.random.key(shape[0]), -2, 2, shape)
                   / math.sqrt(shape[0]), np.float64)
    sp = pair_rows_structured(W, rounding)
    I, J, R = reference.pair_rows(W, rounding)
    assert np.array_equal(I, sp.I) and np.array_equal(J, sp.J) and np.array_equal(R, sp.resid)
    assert sorted(np.concatenate([I, J, R]).tolist()) == list(range(shape[0]))


def test_fp8_control_rounds_more_than_bfloat16():
    x = jax.random.normal(jax.random.key(0), (64, 256))
    e8 = float(jnp.abs(reference.fp8(x, -1) - x).mean())
    e16 = float(jnp.abs(x.astype(jnp.bfloat16).astype(jnp.float32) - x).mean())
    assert e8 > 8 * e16
