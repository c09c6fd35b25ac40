"""The serving engine's slot inputs (each slot's next token and position)
live on the host: forced prompt tokens, admission and release are numpy
writes, and the decode dispatch carries them to the device.

Prompt tails absorbed one forced token per shared decode step, in several
slots at once and across a release and a re-admission, must give each
request exactly the greedy tokens of a one-slot engine that prefills the
whole prompt; and forcing or releasing must touch no device value."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import lm as M
from repro.models.param import unzip
from repro.serving.engine import ServeEngine

MAX_SEQ, BATCH, CHUNK = 32, 3, 3


def _engine(params, cfg, batch_size, gemm="xla"):
    knob_kw = {"gemm": gemm, "pair_rounding": 0.0} if gemm != "xla" else {}
    knobs = M.PerfKnobs(q_chunk=16, k_chunk=16, remat="none", **knob_kw)
    return ServeEngine(cfg, params, max_seq=MAX_SEQ, batch_size=batch_size, knobs=knobs)


@pytest.fixture(scope="module")
def model():
    # fp32: the claim is exact token parity, not bf16 noise
    cfg = dataclasses.replace(get_smoke_config("qwen2-1.5b"), dtype="float32")
    params, _ = unzip(M.init_lm(cfg, jax.random.key(4)))
    # a smaller embedding table: at its initial scale the tiny model's greedy
    # pick is its input token, which would hide a wrong token or position
    params["embed"] = params["embed"] * 0.1
    return cfg, params


def _requests(vocab):
    """(prompt, new tokens): more requests than slots, so slots are released
    and re-admitted while the others are still absorbing their prompts."""
    rng = np.random.default_rng(21)
    shapes = [(11, 4), (7, 2), (9, 5), (3, 3), (10, 3)]
    return [(rng.integers(0, vocab, size=(n,)).astype(np.int32), k) for n, k in shapes]


def _serve(eng, requests):
    """Admit each request with its first ``CHUNK`` tokens prefilled and the
    tail forced one token per shared step (the front end's chunked prefill);
    the step that absorbs a prompt's last token emits its first generated
    token.  Returns each request's generated tokens, in request order."""
    pending = list(enumerate(requests))
    running, outs = {}, {}
    while pending or running:
        for slot in eng.free_slots():
            if not pending:
                break
            i, (prompt, n) = pending.pop(0)
            first = eng.add_request(slot, prompt[:CHUNK])
            running[slot] = st = {"i": i, "prompt": prompt, "fed": CHUNK, "n": n, "out": []}
            if CHUNK < len(prompt):
                eng.force_token(slot, int(prompt[CHUNK]))
            else:
                st["out"].append(first)
        nxt = eng.step()
        for slot, st in list(running.items()):
            plen = len(st["prompt"])
            if st["fed"] < plen:
                st["fed"] += 1
                if st["fed"] < plen:
                    eng.force_token(slot, int(st["prompt"][st["fed"]]))
                    continue
            st["out"].append(int(nxt[slot]))
            if len(st["out"]) == st["n"]:
                outs[st["i"]] = st["out"]
                eng.release_slot(slot)
                del running[slot]
    return [outs[i] for i in range(len(requests))]


@pytest.mark.parametrize("gemm", ["xla", "pallas_paired"])
def test_forced_prompt_tails_match_whole_prompt_prefill(model, gemm):
    cfg, params = model
    requests = _requests(cfg.vocab)
    eng = _engine(params, cfg, BATCH, gemm)
    got = _serve(eng, requests)
    assert eng.counters.forced_tokens == sum(len(p) - CHUNK for p, _ in requests)
    assert eng.counters.releases == len(requests) > BATCH

    ref = _engine(params, cfg, 1, gemm)
    for (prompt, n), tokens in zip(requests, got, strict=True):
        want = ref.generate({0: prompt}, n_steps=n)[0]
        ref.release_slot(0)
        assert tokens == want, f"prompt of {len(prompt)} tokens diverged"


def test_force_and_release_touch_no_device_value(model):
    cfg, params = model
    eng = _engine(params, cfg, 2)
    prompt = _requests(cfg.vocab)[0][0]
    eng.add_request(0, prompt[:CHUNK])
    eng.add_request(1, prompt[:CHUNK])
    with jax.transfer_guard("disallow"):
        eng.force_token(0, int(prompt[CHUNK]))
        eng.release_slot(1, scrub=False)
    assert eng.tokens[0, 0] == prompt[CHUNK]
    assert (eng.pos[1], eng.tokens[1, 0]) == (0, 0) and eng.pos[0] == CHUNK
    eng.step()
    assert eng.pos.tolist() == [CHUNK + 1, 0]


def test_slot_input_writes_do_not_retrace(model):
    cfg, params = model
    eng = _engine(params, cfg, 2)
    prompt = _requests(cfg.vocab)[0][0]
    eng.add_request(0, prompt[:CHUNK])
    eng.step()
    n = eng.counters.traces
    assert n == 2  # the decode and the prefill step, each traced once
    eng.force_token(0, 5)
    eng.step()
    eng.add_request(1, prompt[:CHUNK])
    eng.force_token(1, 6)
    eng.step()
    eng.release_slot(0)
    eng.step()
    eng.release_slot(1, scrub=False)
    eng.add_request(1, prompt[:CHUNK])
    eng.step()
    assert eng.counters.traces == n
