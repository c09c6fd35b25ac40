"""Pallas flash-attention kernel vs the pure-jnp blocked reference."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import decode_attention as da
from repro.kernels.flash_attention import flash_attention_fwd
from repro.models import layers as L
from repro.models.layers import flash_attention


def _mha_ref(q, k, v, causal):
    B, Sq, H, D = q.shape
    KH = k.shape[2]
    G = H // KH
    qf = q.astype(jnp.float32).reshape(B, Sq, KH, G, D)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qf, kf) / np.sqrt(D)
    if causal:
        mask = jnp.tril(jnp.ones((Sq, k.shape[1]), bool))
        s = jnp.where(mask[None, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p, vf)
    return o.reshape(B, Sq, H, D).astype(q.dtype)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize(
    "B,Sq,Sk,H,KH,D,qc,kc",
    [
        (2, 64, 64, 4, 2, 16, 16, 16),
        (1, 128, 128, 2, 2, 32, 32, 64),
        (2, 32, 32, 4, 1, 8, 32, 32),  # single kv head (MQA), one block
    ],
)
def test_flash_kernel_matches_dense_ref(B, Sq, Sk, H, KH, D, qc, kc, causal):
    rng = np.random.default_rng(B * 100 + H)
    q = jnp.asarray(rng.normal(size=(B, Sq, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, Sk, KH, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, Sk, KH, D)), jnp.float32)
    got = flash_attention_fwd(q, k, v, causal=causal, q_chunk=qc, k_chunk=kc)
    want = _mha_ref(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)


def test_flash_kernel_matches_model_flash_path():
    """Kernel == the model's jnp flash path (the thing it replaces on TPU)."""
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(size=(2, 64, 4, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 64, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 64, 2, 16)), jnp.float32)
    got = flash_attention_fwd(q, k, v, causal=True, q_chunk=16, k_chunk=16)
    want = flash_attention(q, k, v, causal=True, q_chunk=16, k_chunk=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("Sq,Sk", [(37, 53), (17, 64), (64, 21)])
def test_flash_kernel_ragged_lengths(Sq, Sk, causal):
    """Lengths the chunk grid does not divide: the kernel pads internally,
    masks the padded key lanes, and slices the output back — no assert on
    ``Sq % q_chunk`` left to vanish under ``python -O``."""
    rng = np.random.default_rng(Sq * 100 + Sk)
    q = jnp.asarray(rng.normal(size=(2, Sq, 4, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, Sk, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, Sk, 2, 16)), jnp.float32)
    got = flash_attention_fwd(q, k, v, causal=causal, q_chunk=16, k_chunk=16)
    want = _mha_ref(q, k, v, causal)
    assert got.shape == want.shape
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_gqa_non_divisible_heads_raise():
    """H % KH != 0 is a loud ValueError, not a silent index-map wraparound."""
    q = jnp.zeros((1, 8, 3, 8), jnp.float32)
    kv = jnp.zeros((1, 8, 2, 8), jnp.float32)
    with pytest.raises(ValueError, match="divide evenly"):
        flash_attention_fwd(q, kv, kv, q_chunk=8, k_chunk=8)
    qd = jnp.zeros((1, 1, 3, 8), jnp.float32)
    with pytest.raises(ValueError, match="divide evenly"):
        da.decode_attention_fwd(qd, kv, kv, jnp.zeros((1,), jnp.int32))


@pytest.mark.parametrize("window,n_sink", [(0, 0), (6, 0), (6, 2)])
@pytest.mark.parametrize("S,k_chunk", [(8, 8), (24, 8), (33, 16)])
def test_decode_kernel_matches_layers_decode(S, k_chunk, window, n_sink):
    """Bare fused decode kernel vs ``layers.decode_attention`` across cache
    lengths (incl. ragged S), slot positions, sliding windows and sinks."""
    rng = np.random.default_rng(S * 10 + window + n_sink)
    B, H, KH, D = 3, 4, 2, 16
    q = jnp.asarray(rng.normal(size=(B, 1, H, D)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(B, S, KH, D)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(B, S, KH, D)), jnp.float32)
    pos = jnp.asarray([0, S // 2, S - 1], jnp.int32)
    got = da.decode_attention_fwd(q, kc, vc, pos, window=window,
                                  n_sink=n_sink, k_chunk=k_chunk)
    want = L.decode_attention(q, kc, vc, pos, window=window, n_sink=n_sink)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_decode_kernel_fully_masked_slot_is_finite_zero():
    """A slot whose mask admits no keys (pos = -1: a fresh/inactive batch
    lane) must flush exact zeros, not NaN from an all--inf softmax row."""
    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.normal(size=(2, 1, 4, 8)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(2, 16, 2, 8)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(2, 16, 2, 8)), jnp.float32)
    pos = jnp.asarray([-1, 7], jnp.int32)
    got = np.asarray(da.decode_attention_fwd(q, kc, vc, pos, k_chunk=8))
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got[0], 0.0)
    want = L.decode_attention(q[1:], kc[1:], vc[1:], pos[1:])
    np.testing.assert_allclose(got[1:], np.asarray(want), rtol=2e-5, atol=2e-5)


def test_flash_kernel_bf16():
    rng = np.random.default_rng(9)
    q = jnp.asarray(rng.normal(size=(1, 64, 2, 32)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(1, 64, 2, 32)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(1, 64, 2, 32)), jnp.bfloat16)
    got = flash_attention_fwd(q, k, v, causal=True, q_chunk=32, k_chunk=32)
    want = _mha_ref(q, k, v, True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=5e-2, atol=5e-2
    )
