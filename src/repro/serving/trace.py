"""Profiler spans of the serving engine.

Each span is a ``jax.profiler.TraceAnnotation``, always on: there is no flag.
With no profiler active, opening one is a check (about a microsecond a span
on a CPU core); under ``jax.profiler.trace`` it becomes a host event on the
profiler's clock, nested inside the span open around it, with its keyword
args as the event's stats.  Args are built only from values the host already
holds, so a span never waits on the device, reads from it or adds an op.

The engine's spans (``ServeEngine``), parents before their children:

* ``engine.admit`` (``slot``, ``req``, ``prompt_tokens``): all of an
  admission, with ``engine.admit.prefill`` (the prefill dispatch, ``traces``),
  ``engine.admit.splice`` (the cache splice and the host write of the
  position) and ``engine.admit.first_token`` (the host read of the first
  token, ``bytes``);
* ``engine.step`` (``step``, ``active``, ``forced``, ``d2h_bytes``): one
  decode step, ``forced`` the ``engine.force`` calls since the previous step;
  with ``engine.step.dispatch`` (the decode dispatch, which carries the
  slots' host-held tokens and positions to the device, ``traces``),
  ``engine.step.sample`` (slice and argmax), ``engine.step.logits_to_host``
  and ``engine.step.tokens_to_host`` (each with its ``bytes``; the tokens are
  read once a step, and become the next step's inputs on the host);
* ``engine.force`` (``slot``, ``req``): one forced prompt token, a host
  write;
* ``engine.release`` (``slot``, ``req``), with ``engine.release.scrub``.

``req`` is the admission ordinal, so a request's admission, its forced
tokens and its release share one id.
"""
from __future__ import annotations

import jax

SPANS = (
    "engine.admit", "engine.admit.prefill", "engine.admit.splice",
    "engine.admit.first_token",
    "engine.step", "engine.step.dispatch", "engine.step.sample",
    "engine.step.logits_to_host", "engine.step.tokens_to_host",
    "engine.force",
    "engine.release", "engine.release.scrub",
)


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A profiler span named ``name`` carrying ``args`` (ints or strings);
    ``set_metadata(**more)`` on the open span adds args known only later."""
    return jax.profiler.TraceAnnotation(name, **args)
