"""LeNet-5 + data pipeline + transform-pass tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.transform import pair_model_params
from repro.data.mnist import load_mnist, pad_to_32, synthetic_mnist, batches
from repro.data.tokens import synthetic_tokens, token_batches
from repro.models.lenet import (
    LENET_CONV_SHAPES,
    init_lenet,
    lenet_apply,
    lenet_loss,
)


def test_lenet_shapes_and_finiteness():
    params = init_lenet(jax.random.key(0))
    x = jnp.zeros((4, 32, 32, 1))
    logits = lenet_apply(params, x)
    assert logits.shape == (4, 10)
    assert bool(jnp.isfinite(logits).all())


def test_lenet_conv_macs_match_paper_baseline():
    """The paper's 405600-mult baseline = sum over conv layers of
    positions × kernel size."""
    total = sum(
        int(np.prod(shape)) * pos for shape, pos in LENET_CONV_SHAPES.values()
    )
    assert total == 405600


def test_lenet_grads_flow():
    params = init_lenet(jax.random.key(0))
    x = jnp.ones((2, 32, 32, 1)) * 0.5
    y = jnp.array([3, 7])
    (loss, acc), grads = jax.value_and_grad(lenet_loss, has_aux=True)(params, x, y)
    assert np.isfinite(float(loss))
    gnorm = sum(float(jnp.abs(g).sum()) for g in jax.tree.leaves(grads))
    assert gnorm > 0


def test_synthetic_mnist_deterministic_and_labeled():
    x1, y1 = synthetic_mnist(64, seed=5)
    x2, y2 = synthetic_mnist(64, seed=5)
    np.testing.assert_array_equal(x1, x2)
    np.testing.assert_array_equal(y1, y2)
    assert x1.shape == (64, 28, 28, 1)
    assert x1.min() >= 0 and x1.max() <= 1
    assert set(np.unique(y1)) <= set(range(10))


def test_synthetic_digits_are_distinguishable():
    """Mean image per class should differ clearly between e.g. 1 and 8."""
    x, y = synthetic_mnist(600, seed=1)
    m1 = x[y == 1].mean(axis=0)
    m8 = x[y == 8].mean(axis=0)
    assert np.abs(m1 - m8).mean() > 0.05


def test_pad_to_32():
    x, _ = synthetic_mnist(2, seed=0)
    assert pad_to_32(x).shape == (2, 32, 32, 1)


def test_load_mnist_reports_source():
    x, y, src = load_mnist("test", synthetic_n=16)
    assert src in ("real", "synthetic")
    assert x.shape[0] == y.shape[0]


def test_batches_deterministic():
    x, y = synthetic_mnist(100, seed=0)
    b1 = list(batches(x, y, 32, seed=3))
    b2 = list(batches(x, y, 32, seed=3))
    assert len(b1) == 3
    for (xa, ya), (xb, yb) in zip(b1, b2, strict=True):
        np.testing.assert_array_equal(xa, xb)
        np.testing.assert_array_equal(ya, yb)


def test_token_stream_deterministic_and_sharded():
    g1 = token_batches(8, 16, 1000, seed=1)
    g2 = token_batches(8, 16, 1000, seed=1)
    t1, l1 = next(g1)
    t2, l2 = next(g2)
    np.testing.assert_array_equal(t1, t2)
    np.testing.assert_array_equal(l1, l2)
    assert t1.shape == (8, 16)
    # labels are next-token shifted
    full = synthetic_tokens(8, 16, 1000, seed=1, step=0)
    np.testing.assert_array_equal(t1, full[:, :-1])
    np.testing.assert_array_equal(l1, full[:, 1:])
    # shards partition the global batch
    s0 = next(token_batches(8, 16, 1000, seed=1, shard_index=0, shard_count=2))
    s1 = next(token_batches(8, 16, 1000, seed=1, shard_index=1, shard_count=2))
    np.testing.assert_array_equal(np.concatenate([s0[0], s1[0]]), t1)


def test_tokens_have_learnable_structure():
    """Bigram entropy must be far below uniform (the stream is learnable)."""
    t = synthetic_tokens(4, 4096, 50, seed=0, step=0).ravel()
    # distribution of next token given current parity bucket
    pairs = np.stack([t[:-1] % 10, t[1:] % 10])
    joint = np.zeros((10, 10))
    np.add.at(joint, (pairs[0], pairs[1]), 1)
    joint /= joint.sum()
    marg = joint.sum(1, keepdims=True) @ joint.sum(0, keepdims=True)
    # mutual information > 0.1 nats
    mi = np.nansum(joint * np.log((joint + 1e-12) / (marg + 1e-12)))
    assert mi > 0.1


def test_pair_model_params_on_lenet():
    params = init_lenet(jax.random.key(0))
    paired, report = pair_model_params(params, rounding=0.05, min_dim=4)
    assert report.total_pairs > 0
    # biases and small dims untouched; conv + fc leaves eligible
    names = [l.path for l in report.leaves]
    assert any("conv1" in n for n in names)
    assert any("fc1" in n for n in names)
    # same treedef, same shapes
    assert jax.tree.structure(paired) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(paired), jax.tree.leaves(params), strict=True):
        assert a.shape == b.shape and a.dtype == b.dtype
    # error bound
    for la, lb in zip(jax.tree.leaves(paired), jax.tree.leaves(params), strict=True):
        assert float(jnp.max(jnp.abs(jnp.asarray(la, jnp.float64) - jnp.asarray(lb, jnp.float64)))) <= 0.025 + 1e-9
    s = report.savings()
    assert 0 <= s["power_saving"] < 1
    assert 0 <= s["pair_fraction"] <= 1


def test_pair_model_params_structured_mode():
    params = {"w": np.random.default_rng(0).normal(size=(64, 32)).astype(np.float32)}
    paired, report = pair_model_params(params, rounding=0.2, mode="structured", keep_pairings=True)
    assert report.leaves[0].pairing is not None
    assert paired["w"].shape == (64, 32)


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_pair_model_params_pairs_bf16_like_fp32(dtype):
    """bf16 leaves (the serving dtype) are paired, not skipped as non-float."""
    w = np.random.default_rng(0).normal(size=(64, 32)).astype(np.float32)
    paired, report = pair_model_params({"w": w.astype(dtype)}, rounding=0.2, min_dim=4)
    _, want = pair_model_params({"w": w.astype(dtype).astype(np.float32)},
                                rounding=0.2, min_dim=4)
    assert report.total_pairs == want.total_pairs > 0
    assert paired["w"].dtype == dtype
