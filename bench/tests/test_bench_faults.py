"""The correctness check sees a broken timed path, and the control, on the
CPU at a size a test run holds (``bench/tests/tiny.py``).

The harness runs whole, past its look for a chip, with the engine broken
underneath; ``correct`` must come out false.  A served model on one chip
can have two of the faults a run must catch: a token altered where it is
produced, and a step that returns its state (the KV cache) unchanged.  The
control is the reference itself in fp8, the precision below the served
bfloat16, judged in the program's place: it too must come out not correct.
"""
import pytest

from bench import run
from bench.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("tiny"))


def alter_tokens(eng):
    """Every fifth step hands back each slot's token plus one."""
    step, n = eng.step, [0]

    def altered(*a, **k):
        out = step(*a, **k)
        n[0] += 1
        if n[0] % 5 == 0:
            out = out.copy()
            out[out >= 0] = (out[out >= 0] + 1) % eng.cfg.vocab
        return out
    eng.step = altered


def stale_cache(eng):
    """The decode step returns the cache it was given."""
    decode = eng._decode
    eng._decode = lambda p, c, t, pos: (decode(p, c, t, pos)[0], c)


@pytest.mark.parametrize("fault", [alter_tokens, stale_cache], ids=lambda f: f.__name__)
def test_a_broken_timed_path_is_not_correct(root, fault):
    r = run.run_cell("tiny.decode", 11, 1.5, False, root=root, require_tpu=False, fault=fault)
    assert r["attempted"] > 0
    assert not r["correct"]
    assert r["check"]["max_logit_gap"]["value"] > r["check"]["max_logit_gap"]["limit"]


def test_the_fp8_control_fails_the_limit(root):
    """The control, judged in the program's place, comes out not correct,
    while the program's own picks in the same run keep to the limit."""
    r = run.run_cell("tiny.decode", 12, 1.5, False, root=root, require_tpu=False, control=True)
    c = r["check"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert not r["correct"]
    assert c["max_logit_gap"]["value"] > c["max_logit_gap"]["limit"]
    assert c["program_max_logit_gap"]["value"] <= c["max_logit_gap"]["limit"]
    assert c["max_logit_gap"]["value"] >= 3 * c["program_max_logit_gap"]["value"]
