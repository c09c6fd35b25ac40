"""Closed-loop wall-clock client over the serving engine's public API.

One client per slot: each sends the next request of its own stream
(``traffic.clients``) the moment its last one completes.  Admission
follows the front end's convention (``ServeFrontend._admit``): the first
``prefill_chunk`` prompt tokens go through prefill, and the tail rides the
shared decode steps one token per step, forced in with ``force_token``.
The step that absorbs the last prompt token emits the first generated
token.  The client adds no other policy.

Every send, first token and generated token gets a host timestamp, taken
when the engine call that produced it has returned (each ends in a host
read).  Each request also keeps the token the engine ranked first at every
position it decoded, the prompt positions whose pick the next prompt token
overrides included (``picks``): what the correctness check holds to the
reference.  With ``annotate`` each engine call and the client's own work
are wrapped in ``jax.profiler.TraceAnnotation`` spans (``admit``, ``step``,
``force``, ``release``, ``client``), one after another, so idle gaps on the
device can be attributed.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time

import jax
import numpy as np


@dataclasses.dataclass
class Served:
    """One request as the client saw it."""

    req: object  # traffic.Request
    slot: int
    send: float
    pos: int = 0  # tokens in the slot's cache
    fed: int = 0  # prompt tokens fed so far
    first: float = float("nan")
    done: float = float("nan")
    tokens: list = dataclasses.field(default_factory=list)
    times: list = dataclasses.field(default_factory=list)
    picks: list = dataclasses.field(default_factory=list)  # from position prefill_chunk - 1
    bad_token: bool = False


@dataclasses.dataclass
class Step:
    start: float
    end: float
    active: int  # positions processed (one per active slot)
    context: int  # keys attended, summed over active slots
    generated: int


@dataclasses.dataclass
class Admit:
    start: float
    end: float
    tokens: int  # prompt tokens prefilled


class ClosedLoop:
    def __init__(self, engine, clients, prefill_chunk: int, vocab: int,
                 annotate: bool = False, clock=time.perf_counter):
        self.eng = engine
        self.clients = clients  # one request iterator per slot
        self.c0 = prefill_chunk
        self.vocab = vocab
        self.annotate = annotate
        self.clock = clock
        self.running: dict[int, Served] = {}
        self.served: list[Served] = []
        self.steps: list[Step] = []
        self.admits: list[Admit] = []
        self.completed = 0

    def span(self, name: str):
        return jax.profiler.TraceAnnotation(name) if self.annotate else contextlib.nullcontext()

    # -- the loop ----------------------------------------------------------
    def fill(self) -> None:
        """Every free slot's client sends its next request."""
        for slot in self.eng.free_slots():
            self._send(slot)

    def run_until(self, done) -> None:
        while not done():
            self.fill()
            self.step()

    def warm(self) -> None:
        """Run until as many requests have completed as there are slots.

        By then every shape the window uses has run (prefill, decode,
        admission, release), and the slots no longer start in step.
        """
        n = self.eng.batch_size
        self.run_until(lambda: self.completed >= n)

    def window(self, seconds: float) -> tuple[float, float]:
        """Run for ``seconds`` of wall clock; returns (open, close) of the window."""
        t0 = self.clock()
        self.run_until(lambda: self.clock() - t0 >= seconds)
        return t0, self.clock()

    # -- engine calls --------------------------------------------------------
    def _send(self, slot: int) -> None:
        req = next(self.clients[slot])
        plen = len(req.prompt)
        s = Served(req, slot, send=self.clock())
        with self.span("admit"):
            first = self.eng.add_request(slot, req.prompt[:self.c0])
            if self.c0 < plen:
                self.eng.force_token(slot, int(req.prompt[self.c0]))
        t = self.clock()
        self.admits.append(Admit(s.send, t, self.c0))
        s.pos = s.fed = self.c0
        s.picks.append(int(first))
        self.running[slot] = s
        self.served.append(s)
        if self.c0 == plen and self._emit(s, int(first), t):
            self._release(s)

    def step(self) -> None:
        t0 = self.clock()
        with self.span("step"):
            nxt = self.eng.step()
        t1 = self.clock()
        with self.span("client"):
            active = len(self.running)
            context = sum(s.pos + 1 for s in self.running.values())
            generated = 0
            forced, finished = [], []
            for slot, s in self.running.items():
                tok = int(nxt[slot])
                s.picks.append(tok)
                s.pos += 1
                plen = len(s.req.prompt)
                if s.fed < plen:
                    s.fed += 1
                    if s.fed < plen:
                        forced.append((slot, int(s.req.prompt[s.fed])))
                        continue
                generated += 1
                if self._emit(s, tok, t1):
                    finished.append(s)
        self.steps.append(Step(t0, t1, active, context, generated))
        if forced:
            with self.span("force"):
                for slot, tok in forced:
                    self.eng.force_token(slot, tok)
        for s in finished:
            self._release(s)

    def _emit(self, s: Served, tok: int, t: float) -> bool:
        """Record a generated token; True when the request is complete."""
        if not 0 <= tok < self.vocab:
            s.bad_token = True
        if not s.tokens:
            s.first = t
        s.tokens.append(tok)
        s.times.append(t)
        if len(s.tokens) < s.req.max_new:
            return False
        s.done = t
        return True

    def _release(self, s: Served) -> None:
        with self.span("release"):
            self.eng.release_slot(s.slot)
        del self.running[s.slot]
        self.completed += 1


def in_window(t: float, t0: float, t1: float) -> bool:
    return t0 < t <= t1


def percentile(values, q: float) -> float:
    """The q-th percentile (linear interpolation); NaN when there are none."""
    return float(np.percentile(np.asarray(values, np.float64), q)) if len(values) else float("nan")
