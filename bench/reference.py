"""What every plain reference of the benchmark shares.

A configuration file names its architecture, and ``bench/references/
<architecture>.py`` holds that architecture's float32 forward
(``bench/references/__init__.py`` lists what each such module exports).
This module holds the pieces that do not depend on the architecture:

* the subtractor pairing, worked out again by the benchmark's own code
  from the rule the program implements (structured pairing: one row
  pairing shared by every column of a weight, ``y = (x[I] - x[J]) K +
  x[R] W_R`` with ``K = (W[I] - W[J]) / 2``), and the effective weights
  ``W[I] = +K, W[J] = -K, W[R] = W[R]`` a forward applies in float32;
* the arithmetic of a forward: ``matmul`` (in fp8 operands for the
  control: e4m3, per-token and per-channel scales, the precision below the
  configurations' bfloat16), ``rms_norm`` and rotate-half ``rope``;
* the teacher-forced reading of a served run: the padded batch of whole
  sequences, and, a block of positions at a time, how far the logit of the
  token a server ranked first lies below the reference's best.

Nothing here imports the program or takes anything the program made.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

#: the program leaves a weight unpaired when either GEMM dim is below this
MIN_DIM = 8
#: positions whose logits are built at once (bounds the (n, c, vocab) block)
LOGIT_CHUNK = 128
#: most diagonal steps of the pairing walk evaluated per numpy call
WALK_CHUNK = 512


def padded_vocab(vocab: int) -> int:
    """Rows of the embedding table: the vocabulary rounded up to 128."""
    return -(-vocab // 128) * 128


# ---------------------------------------------------------------------------
# structured pairing (the paper's pair walk over row profiles)
# ---------------------------------------------------------------------------


def pair_rows(W: np.ndarray, rounding: float) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Row pairing shared by every column of ``W`` (K, N): (I, J, R).

    Rows with a positive mean, in ascending order of mean, are walked against
    rows with a negative mean, in ascending order of |mean|.  At each step,
    with p the positive mean and m the negative mean's magnitude: p >= m + r
    retires the negative row, p <= m - r the positive one; otherwise the two
    pair when rms(W[i] + W[j]) < r, and if not, the row with the smaller
    magnitude is retired.  Rows left over, and zero-mean rows, are residual.
    The walk is evaluated a chunk of diagonal steps at a time; the chunk
    doubles while every step pairs and starts small again after one that
    does not.
    """
    W = np.asarray(W, dtype=np.float64)
    mean = W.mean(axis=1)
    pos = np.nonzero(mean > 0)[0]
    neg = np.nonzero(mean < 0)[0]
    zero = np.nonzero(mean == 0)[0]
    pos = pos[np.argsort(mean[pos], kind="stable")]
    neg = neg[np.argsort(-mean[neg], kind="stable")]
    I: list[np.ndarray] = []
    J: list[np.ndarray] = []
    R: list[int] = []
    pp = pn = 0
    chunk = 8
    while pp < len(pos) and pn < len(neg):
        n = min(chunk, len(pos) - pp, len(neg) - pn)
        i, j = pos[pp:pp + n], neg[pn:pn + n]
        p, m = mean[i], -mean[j]
        s = W[i] + W[j]
        err = np.sqrt(np.mean(s * s, axis=1))
        pairs = ~(p >= m + rounding) & ~(p <= m - rounding) & (err < rounding)
        k = int(np.argmin(pairs)) if not pairs.all() else n
        I.append(i[:k])
        J.append(j[:k])
        pp += k
        pn += k
        if k == n:
            chunk = min(2 * chunk, WALK_CHUNK)
            continue
        chunk = 8
        if p[k] >= m[k] + rounding:
            R.append(int(j[k]))
            pn += 1
        elif p[k] <= m[k] - rounding or p[k] <= m[k]:
            R.append(int(i[k]))
            pp += 1
        else:
            R.append(int(j[k]))
            pn += 1
    R.extend(int(x) for x in pos[pp:])
    R.extend(int(x) for x in neg[pn:])
    R.extend(int(x) for x in zero)
    cat = lambda parts: np.concatenate(parts) if parts else np.zeros(0, np.int64)
    return cat(I).astype(np.int64), cat(J).astype(np.int64), np.sort(np.asarray(R, np.int64))


def row_mix(K: int, I: np.ndarray, J: np.ndarray):
    """(a, b, partner) with effective row k = a[k] W[k] + b[k] W[partner[k]]:
    paired rows become +/-(W[I] - W[J]) / 2, the others stay as they are."""
    a = np.ones(K, np.float32)
    b = np.zeros(K, np.float32)
    partner = np.arange(K, dtype=np.int32)
    a[I] = a[J] = 0.5
    b[I] = b[J] = -0.5
    partner[I], partner[J] = J, I
    return a, b, partner


@jax.jit
def effective_weight(W, a, b, partner):
    """float32 effective weight of a (K, N) matrix from its row mix."""
    W = W.astype(jnp.float32)
    return a[:, None] * W + b[:, None] * W[partner]


# ---------------------------------------------------------------------------
# the arithmetic of a forward
# ---------------------------------------------------------------------------


def fp8(x: jax.Array, axis: int) -> jax.Array:
    """x rounded through float8 e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def matmul(x: jax.Array, w: jax.Array, low: bool) -> jax.Array:
    """x (..., K) @ w (K, N); in fp8 operands when ``low``."""
    if low:
        x, w = fp8(x, -1), fp8(w, 0)
    return jnp.einsum("...k,kn->...n", x, w)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope(x, theta):
    """Rotate-half rotary embedding over positions 0..S-1. x: (n, S, heads, hd)."""
    S, hd = x.shape[1], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


# ---------------------------------------------------------------------------
# the teacher-forced reading of a served run
# ---------------------------------------------------------------------------


def teacher_forced(sequences: list, shape: tuple[int, int] | None):
    """Tokens and targets (n, S) of a padded batch of whole sequences.

    ``sequences``: per request ``(prompt, served, start, picks)``; the row
    holds prompt + served[:-1], and the target at each position from
    ``start`` on is the token the server ranked first there (-1 elsewhere).
    ``shape`` (rows, positions) pads the batch to a fixed size, so that
    every run of a cell reuses the same compiled programs.
    """
    n = len(sequences)
    S = max(len(p) + len(t) - 1 for p, t, _, _ in sequences)
    if shape is not None:
        if shape[0] < n or shape[1] < S:
            raise ValueError(f"{n} sequences of up to {S} positions exceed {shape}")
        n, S = shape
    toks = np.zeros((n, S), np.int32)
    targets = np.full((n, S), -1, np.int32)
    for r, (p, t, start, picks) in enumerate(sequences):
        seq = np.concatenate([p, t[:-1]]).astype(np.int32)
        if len(picks) != len(seq) - start:
            raise ValueError(
                f"request {r}: {len(picks)} picks for positions {start}..{len(seq) - 1}")
        toks[r, :len(seq)] = seq
        targets[r, start:len(seq)] = picks
    return toks, targets


def chunked_gaps(targets: np.ndarray, ref_logits, low_logits=None):
    """(gap, control gap), each (n, S) float64, read a block of positions at a time.

    ``ref_logits(sl)`` gives the reference's logits (n, c, V) at the
    positions ``sl``; the gap there is how far the target's logit lies
    below the reference's best.  With ``low_logits``, the control's logits
    at the same positions, the control gap is that of the token the control
    ranks first; without, it stays zero.
    """
    n, S = targets.shape
    gap = np.zeros((n, S), np.float64)
    gap_low = np.zeros((n, S), np.float64)
    tgt = jnp.asarray(targets)
    for c0 in range(0, S, LOGIT_CHUNK):
        sl = slice(c0, min(S, c0 + LOGIT_CHUNK))
        ref = ref_logits(sl)
        best = ref.max(-1)
        t = tgt[:, sl]
        picked = jnp.take_along_axis(ref, jnp.maximum(t, 0)[..., None], -1)[..., 0]
        gap[:, sl] = np.asarray(best - picked)
        if low_logits is not None:
            pick = jnp.argmax(low_logits(sl), axis=-1)
            low_picked = jnp.take_along_axis(ref, pick[..., None], -1)[..., 0]
            gap_low[:, sl] = np.asarray(best - low_picked)
    return gap, gap_low


def gaps_result(n: int, targets: np.ndarray, gap: np.ndarray, gap_low: np.ndarray | None,
                clock: dict) -> dict:
    """``served_gaps``' result for the first ``n`` rows: ``{"gaps": [per
    request array], "seconds": {...}}`` (where the time went, from the
    ``clock`` marks ``start``, ``weights``, ``pairing`` and ``forward``),
    and ``"control_gaps"`` where ``gap_low`` is given."""
    out = {"gaps": [gap[r][targets[r] >= 0] for r in range(n)],
           "seconds": {k: clock[k] - clock[p] for p, k in
                       (("start", "weights"), ("weights", "pairing"), ("pairing", "forward"))}}
    if gap_low is not None:
        out["control_gaps"] = [gap_low[r][targets[r] >= 0] for r in range(n)]
    return out
