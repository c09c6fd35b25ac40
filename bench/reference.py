"""Plain float32 reference of the served dense GQA decoder.

It imports nothing of the program and takes nothing the program made: the
weights are drawn again from the seed (``bench.weights``), and the
subtractor pairing is worked out again by its own code from the rule the
program implements (structured pairing: one row pairing shared by every
column of a weight, ``y = (x[I] - x[J]) K + x[R] W_R`` with
``K = (W[I] - W[J]) / 2``).  The forward then applies the effective
weights ``W[I] = +K, W[J] = -K, W[R] = W[R]`` in float32 under
``default_matmul_precision("highest")``.

The equations are those of the configuration file as it is run: RMSNorm,
rotate-half RoPE, grouped-query attention with an optional QKV bias, a
SwiGLU MLP, tied embeddings, and the file's embedding, attention, residual
and logit multipliers.

It runs layer by layer over a padded batch of whole sequences (causal
attention, so padding after a sequence never reaches it), so the only
float32 weights on the device at a time are one layer's.  ``served_gaps``
reads, at each position the server decoded, how far the logit of the token
the server ranked first lies below the reference's best; with
``control=True`` the same forward runs a second time with every linear
layer in fp8 (e4m3, per-token and per-channel scales), the precision below
the configuration's bfloat16, and reads the gap of the token that the fp8
forward ranks first.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights

#: weights the program pairs, as (sub-block, name), in the order they run
PAIRED = (("attn", "wq"), ("attn", "wk"), ("attn", "wv"), ("attn", "wo"),
          ("mlp", "w_gate"), ("mlp", "w_up"), ("mlp", "w_down"))
#: the program leaves a weight unpaired when either GEMM dim is below this
MIN_DIM = 8
#: positions whose logits are built at once (bounds the (n, c, vocab) block)
LOGIT_CHUNK = 128
#: most diagonal steps of the pairing walk evaluated per numpy call
WALK_CHUNK = 512


def padded_vocab(vocab: int) -> int:
    """Rows of the embedding table: the vocabulary rounded up to 128."""
    return -(-vocab // 128) * 128


def sizes(cfg: dict) -> dict:
    """The sizes of a configuration file, under short names."""
    d = cfg["hidden_size"]
    H = cfg["num_attention_heads"]
    return {
        "L": cfg["num_hidden_layers"], "d": d, "H": H,
        "KH": cfg["num_key_value_heads"], "hd": cfg.get("head_dim", d // H),
        "f": cfg["intermediate_size"], "V": cfg["vocab_size"],
        "Vp": padded_vocab(cfg["vocab_size"]),
    }


def layout(cfg: dict) -> dict[str, tuple[int, ...]]:
    """Path -> shape of every weight, stacked over layers."""
    s = sizes(cfg)
    L, d, H, KH, hd, f = s["L"], s["d"], s["H"], s["KH"], s["hd"], s["f"]
    seg = "segments/0/"
    out = {
        "embed": (s["Vp"], d),
        "final_norm/scale": (d,),
        seg + "ln1/scale": (L, d),
        seg + "attn/wq": (L, d, H, hd),
        seg + "attn/wk": (L, d, KH, hd),
        seg + "attn/wv": (L, d, KH, hd),
        seg + "attn/wo": (L, H, hd, d),
        seg + "ln2/scale": (L, d),
        seg + "mlp/w_gate": (L, d, f),
        seg + "mlp/w_up": (L, d, f),
        seg + "mlp/w_down": (L, f, d),
    }
    if cfg["attention_bias"]:
        out.update({seg + "attn/bq": (L, H, hd), seg + "attn/bk": (L, KH, hd),
                    seg + "attn/bv": (L, KH, hd)})
    return out


def gemm_view(name: str, w: np.ndarray) -> np.ndarray:
    """(K, N) GEMM view of one layer's weight."""
    if name == "wo":
        return w.reshape(-1, w.shape[-1])
    return w.reshape(w.shape[0], -1)


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
# forward
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


def make_layer(cfg: dict, low: bool):
    """One decoder layer over a padded batch (n, S, d), jitted."""
    s = sizes(cfg)
    H, KH, hd = s["H"], s["KH"], s["hd"]
    eps = cfg["rms_norm_eps"]
    att_mult = cfg["attention_multiplier"]
    res_mult = cfg["residual_multiplier"]
    theta = cfg["rope_theta"]

    @jax.jit
    def layer(h, w):
        n, S, _ = h.shape
        x = rms_norm(h, w["ln1"], eps)
        q = matmul(x, w["wq"], low).reshape(n, S, H, hd)
        k = matmul(x, w["wk"], low).reshape(n, S, KH, hd)
        v = matmul(x, w["wv"], low).reshape(n, S, KH, hd)
        if "bq" in w:
            q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
        q, k = rope(q, theta), rope(k, theta)
        q = q.reshape(n, S, KH, H // KH, hd)  # query head h reads kv head h // G
        scores = jnp.einsum("nqkgd,nskd->nkgqs", q, k) * att_mult
        causal = jnp.tril(jnp.ones((S, S), bool))
        scores = jnp.where(causal, scores, -jnp.inf)
        attn = jnp.einsum("nkgqs,nskd->nqkgd", jax.nn.softmax(scores, axis=-1), v)
        h = h + res_mult * matmul(attn.reshape(n, S, H * hd), w["wo"], low)
        x = rms_norm(h, w["ln2"], eps)
        g = jax.nn.silu(matmul(x, w["w_gate"], low))
        return h + res_mult * matmul(g * matmul(x, w["w_up"], low), w["w_down"], low)

    return layer


def make_head(cfg: dict, low: bool):
    """Final norm + tied head over a block of positions: (n, c, d) -> (n, c, V)."""
    eps, V = cfg["rms_norm_eps"], cfg["vocab_size"]
    scale = 1.0 / cfg["logits_scaling"]

    @jax.jit
    def head(h, norm, embed):
        hn = rms_norm(h, norm, eps)
        return matmul(hn, embed[:V].T, low) * scale

    return head


def pairings(cfg: dict, params: dict, rounding: float) -> dict:
    """Row mix of every paired weight of every layer, worked out on the host."""
    out = {}
    if rounding <= 0:
        return out
    for sub, name in PAIRED:
        stack = np.asarray(params[f"segments/0/{sub}/{name}"])
        for l in range(stack.shape[0]):
            W2 = gemm_view(name, stack[l])
            if min(W2.shape) >= MIN_DIM:
                I, J, _ = pair_rows(W2.astype(np.float64), rounding)
                out[name, l] = row_mix(W2.shape[0], I, J)
    return out


def _layer_weights(params, l, mixes):
    """Effective float32 weights of layer ``l``."""
    seg = "segments/0/"
    w = {"ln1": params[seg + "ln1/scale"][l].astype(jnp.float32),
         "ln2": params[seg + "ln2/scale"][l].astype(jnp.float32)}
    for sub, name in PAIRED:
        W = params[f"{seg}{sub}/{name}"][l]
        W2 = W.reshape(-1, W.shape[-1]) if name == "wo" else W.reshape(W.shape[0], -1)
        if (name, l) in mixes:
            w[name] = effective_weight(W2, *mixes[name, l])
        else:
            w[name] = W2.astype(jnp.float32)
    for b in ("bq", "bk", "bv"):
        if seg + "attn/" + b in params:
            w[b] = params[seg + "attn/" + b][l].astype(jnp.float32)
    return w


def served_gaps(cfg: dict, seed: int, rounding: float, sequences: list,
                *, control: bool = False, params: dict | None = None,
                shape: tuple[int, int] | None = None) -> dict:
    """Teacher-forced gaps of the tokens a server ranked first.

    ``sequences``: per request ``(prompt, served, start, picks)``: the
    prompt, the tokens served, and the token the server ranked first at
    each position from ``start`` on (a prompt position's pick is what the
    server would have said there; from the prompt's last position on, the
    picks are the served tokens).  The reference runs once over prompt +
    served[:-1]; at each judged position it reads ``max(ref) - ref[pick]``.
    ``shape`` (rows, positions) pads the batch to a fixed size, so that
    every run of a cell reuses the same compiled programs.  Returns
    ``{"gaps": [per request array], "seconds": {...}}`` (where the time
    went), and with ``control`` also ``"control_gaps"``: the same read for
    the token the fp8 forward ranks first.
    """
    with jax.default_matmul_precision("highest"):
        return _served_gaps(cfg, seed, rounding, sequences, control, params, shape)


def _served_gaps(cfg, seed, rounding, sequences, control, params, shape):
    import time

    clock = {"start": time.perf_counter()}
    s = sizes(cfg)
    if params is None:
        params = weights.make(seed, layout(cfg), cfg["torch_dtype"])
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
    embed = params["embed"].astype(jnp.float32)
    h = embed[jnp.asarray(toks)] * cfg["embedding_multiplier"]
    streams = {"ref": h}
    if control:
        streams["low"] = h
    layers = {k: make_layer(cfg, k == "low") for k in streams}
    clock["weights"] = time.perf_counter()
    mixes = pairings(cfg, params, rounding)
    clock["pairing"] = time.perf_counter()
    for l in range(s["L"]):
        w = _layer_weights(params, l, mixes)
        streams = {k: layers[k](v, w) for k, v in streams.items()}
        del w
    norm = params["final_norm/scale"].astype(jnp.float32)
    heads = {k: make_head(cfg, k == "low") for k in streams}

    gap = np.zeros((n, S), np.float64)
    gap_low = np.zeros((n, S), np.float64)
    tgt = jnp.asarray(targets)
    for c0 in range(0, S, LOGIT_CHUNK):
        sl = slice(c0, min(S, c0 + LOGIT_CHUNK))
        ref = heads["ref"](streams["ref"][:, sl], norm, embed)
        best = ref.max(-1)
        t = tgt[:, sl]
        picked = jnp.take_along_axis(ref, jnp.maximum(t, 0)[..., None], -1)[..., 0]
        gap[:, sl] = np.asarray(best - picked)
        if control:
            low = heads["low"](streams["low"][:, sl], norm, embed)
            pick = jnp.argmax(low, axis=-1)
            low_picked = jnp.take_along_axis(ref, pick[..., None], -1)[..., 0]
            gap_low[:, sl] = np.asarray(best - low_picked)
    clock["forward"] = time.perf_counter()
    n = len(sequences)
    out = {"gaps": [gap[r][targets[r] >= 0] for r in range(n)],
           "seconds": {k: clock[k] - clock[p] for p, k in
                       (("start", "weights"), ("weights", "pairing"), ("pairing", "forward"))}}
    if control:
        out["control_gaps"] = [gap_low[r][targets[r] >= 0] for r in range(n)]
    return out


def logits(cfg: dict, seed: int, rounding: float, tokens: np.ndarray,
           params: dict | None = None) -> np.ndarray:
    """Reference logits (n, S, V) of token sequences (n, S), for tests."""
    with jax.default_matmul_precision("highest"):
        if params is None:
            params = weights.make(seed, layout(cfg), cfg["torch_dtype"])
        s = sizes(cfg)
        embed = params["embed"].astype(jnp.float32)
        h = embed[jnp.asarray(tokens)] * cfg["embedding_multiplier"]
        layer = make_layer(cfg, False)
        mixes = pairings(cfg, params, rounding)
        for l in range(s["L"]):
            h = layer(h, _layer_weights(params, l, mixes))
        norm = params["final_norm/scale"].astype(jnp.float32)
        return np.asarray(make_head(cfg, False)(h, norm, embed))

