"""Plain float32 reference of the served dense GQA decoder.

It imports nothing of the program and takes nothing the program made: the
weights are drawn again from the seed (``bench.weights``), and the
subtractor pairing is worked out again by the benchmark's own pair walk
(``bench.reference``).  The forward applies the effective weights in
float32 under ``default_matmul_precision("highest")``.

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
layer in fp8, and reads the gap of the token that the fp8 forward ranks
first.

The work counts beside it (``gemms``, ``flops_per_position``,
``attention_work``, ``head_flops``) are of the work the algorithm needs,
as the metric readers count it (``bench/work.py``).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference as common
from bench import weights, work

#: weights the program pairs, as (sub-block, name), in the order they run
PAIRED = (("attn", "wq"), ("attn", "wk"), ("attn", "wv"), ("attn", "wo"),
          ("mlp", "w_gate"), ("mlp", "w_up"), ("mlp", "w_down"))


def sizes(cfg: dict) -> dict:
    """The sizes of a configuration file, under short names."""
    d = cfg["hidden_size"]
    H = cfg["num_attention_heads"]
    return {
        "L": cfg["num_hidden_layers"], "d": d, "H": H,
        "KH": cfg["num_key_value_heads"], "hd": cfg.get("head_dim", d // H),
        "f": cfg["intermediate_size"], "V": cfg["vocab_size"],
        "Vp": common.padded_vocab(cfg["vocab_size"]),
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
# work
# ---------------------------------------------------------------------------


def gemms(cfg: dict) -> dict[str, tuple[int, int, bool]]:
    """(K, N, fused residual) of each paired weight: the out-projection and
    the MLP's down-projection carry the sublayer skip connection."""
    s = sizes(cfg)
    d, f, q, kv = s["d"], s["f"], s["H"] * s["hd"], s["KH"] * s["hd"]
    return {"wq": (d, q, False), "wk": (d, kv, False), "wv": (d, kv, False),
            "wo": (q, d, True), "w_gate": (d, f, False), "w_up": (d, f, False),
            "w_down": (f, d, True)}


def flops_per_position(cfg: dict) -> float:
    """Matmul operations of one token position through the decoder stack,
    as a dense model computes them (pairing changes how, not how much)."""
    s = sizes(cfg)
    L, d, H, KH, hd, f = s["L"], s["d"], s["H"], s["KH"], s["hd"], s["f"]
    per_layer = 2.0 * (d * H * hd + 2 * d * KH * hd + H * hd * d + 3 * d * f)
    return L * per_layer


def attention_work(cfg: dict, context: int, rows: int) -> work.Work:
    """One layer's attention for ``rows`` query positions over ``context``
    keys (the keys each attends, summed): score and value products, a read
    of each live key and value, and the attended values written."""
    s = sizes(cfg)
    H, KH, hd = s["H"], s["KH"], s["hd"]
    return work.Work(4.0 * H * hd * context,
                     (2 * KH * hd * context + rows * H * hd) * work.ACT_BYTES)


def head_flops(cfg: dict) -> float:
    """The tied output head for one position."""
    return 2.0 * cfg["hidden_size"] * cfg["vocab_size"]


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def make_layer(cfg: dict, low: bool):
    """One decoder layer over a padded batch (n, S, d), jitted."""
    s = sizes(cfg)
    H, KH, hd = s["H"], s["KH"], s["hd"]
    eps = cfg["rms_norm_eps"]
    att_mult = cfg["attention_multiplier"]
    res_mult = cfg["residual_multiplier"]
    theta = cfg["rope_theta"]
    rms_norm, matmul, rope = common.rms_norm, common.matmul, common.rope

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
        hn = common.rms_norm(h, norm, eps)
        return common.matmul(hn, embed[:V].T, low) * scale

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
            if min(W2.shape) >= common.MIN_DIM:
                I, J, _ = common.pair_rows(W2.astype(np.float64), rounding)
                out[name, l] = common.row_mix(W2.shape[0], I, J)
    return out


def _layer_weights(params, l, mixes):
    """Effective float32 weights of layer ``l``."""
    seg = "segments/0/"
    w = {"ln1": params[seg + "ln1/scale"][l].astype(jnp.float32),
         "ln2": params[seg + "ln2/scale"][l].astype(jnp.float32)}
    for sub, name in PAIRED:
        W2 = gemm_view(name, params[f"{seg}{sub}/{name}"][l])
        if (name, l) in mixes:
            w[name] = common.effective_weight(W2, *mixes[name, l])
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
    clock = {"start": time.perf_counter()}
    if params is None:
        params = weights.make(seed, layout(cfg), cfg["torch_dtype"])
    toks, targets = common.teacher_forced(sequences, shape)
    embed = params["embed"].astype(jnp.float32)
    h = embed[jnp.asarray(toks)] * cfg["embedding_multiplier"]
    streams = {"ref": h}
    if control:
        streams["low"] = h
    layers = {k: make_layer(cfg, k == "low") for k in streams}
    clock["weights"] = time.perf_counter()
    mixes = pairings(cfg, params, rounding)
    clock["pairing"] = time.perf_counter()
    for l in range(cfg["num_hidden_layers"]):
        w = _layer_weights(params, l, mixes)
        streams = {k: layers[k](v, w) for k, v in streams.items()}
        del w
    norm = params["final_norm/scale"].astype(jnp.float32)
    heads = {k: make_head(cfg, k == "low") for k in streams}
    read = {k: (lambda sl, k=k: heads[k](streams[k][:, sl], norm, embed)) for k in streams}
    gap, gap_low = common.chunked_gaps(targets, read["ref"], read.get("low"))
    clock["forward"] = time.perf_counter()
    return common.gaps_result(len(sequences), targets, gap, gap_low if control else None, clock)


def logits(cfg: dict, seed: int, rounding: float, tokens: np.ndarray,
           params: dict | None = None) -> np.ndarray:
    """Reference logits (n, S, V) of token sequences (n, S), for tests."""
    with jax.default_matmul_precision("highest"):
        if params is None:
            params = weights.make(seed, layout(cfg), cfg["torch_dtype"])
        embed = params["embed"].astype(jnp.float32)
        h = embed[jnp.asarray(tokens)] * cfg["embedding_multiplier"]
        layer = make_layer(cfg, False)
        mixes = pairings(cfg, params, rounding)
        for l in range(cfg["num_hidden_layers"]):
            h = layer(h, _layer_weights(params, l, mixes))
        norm = params["final_norm/scale"].astype(jnp.float32)
        return np.asarray(make_head(cfg, False)(h, norm, embed))
