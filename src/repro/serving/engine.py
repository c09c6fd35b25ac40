"""Batched serving engine: prefill once, decode step-by-step.

The engine owns a fixed-capacity batch of sequence slots (continuous-batching
style): each slot tracks its own position, so requests of different lengths
decode together; a finished slot is refilled by the next request without
recompiling (positions are data, not shapes).

This is the single-host reference engine; the pjit'd distributed variant
reuses exactly these step functions through ``launch/steps.build_serve_step``
(same ``decode_step``, sharded cache).
"""
from __future__ import annotations

import dataclasses
from collections.abc import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.kernels.ops import perf_context
from repro.models import lm as M
from repro.models.param import unzip
from repro.serving.trace import span


class CapacityError(ValueError):
    """A request or decode step would exceed the engine's hard bounds.

    Raised instead of letting JAX scatter semantics silently clamp an
    out-of-range cache write into the last row (which corrupts the newest
    cached position for the slot without any error).
    """


#: token emitted for slots that are not active — callers must never treat it
#: as model output (vocab ids are non-negative, so -1 can't collide)
INACTIVE_TOKEN = -1


@dataclasses.dataclass
class EngineCounters:
    """Running totals of an engine's work, for operators.  The engine's
    profiler spans (``repro.serving.trace``) carry the same values as args."""

    steps: int = 0
    admissions: int = 0
    forced_tokens: int = 0
    releases: int = 0
    #: every byte the engine copied to the host (the arrays' ``nbytes``)
    d2h_bytes: int = 0
    #: runs of the Python bodies of the jitted decode and prefill steps: each
    #: is a trace, so growth after warm-up is a recompile
    traces: int = 0


@dataclasses.dataclass
class ServeEngine:
    cfg: ModelConfig
    params: dict
    max_seq: int
    batch_size: int
    knobs: M.PerfKnobs = M.DEFAULT_KNOBS
    #: optional (mesh, rules) — when a mesh is given the engine becomes the
    #: distributed variant: params are paired *per TP shard* and placed with
    #: their pairing metadata beside the weight shards, the cache is
    #: sequence-sharded, and the decode/prefill steps are pjit'd
    #: (launch.steps.wire_serve_cell).  Same slot machinery either way.
    mesh: object = None
    rules: object = None

    def __post_init__(self):
        # attn == "pallas_fused" routes single-token decode attention through
        # the Pallas decode-attention kernel whose attended output feeds the
        # paired out-projection epilogue in VMEM (kernels.decode_attention) —
        # one fewer HBM writeback per decoder layer.  Validate here rather
        # than letting attn_context silently fall back to the dense path on a
        # typo'd knob.
        if self.knobs.attn not in ("xla", "pallas_fused"):
            raise ValueError(
                f"unknown knobs.attn {self.knobs.attn!r} "
                "(expected 'xla' or 'pallas_fused')")
        if self.mesh is not None and self.knobs.attn != "xla":
            raise NotImplementedError(
                "attn='pallas_fused' is single-host only: the sharded serve "
                "cell decodes against a sequence-sharded cache, and the fused "
                "decode-attention kernel has no cross-shard softmax yet — "
                "the mesh path keeps the dense decode attention")
        self.counters = EngineCounters()
        # admission ordinal of each slot's request (-1: never admitted)
        self.slot_req = [-1] * self.batch_size
        cache_tree = M.init_cache(self.cfg, self.batch_size, self.max_seq)
        self.cache, _ = unzip(cache_tree)
        # each slot's position and next input token live on the host, which
        # reads every step's tokens anyway: admission, forced tokens and
        # release are numpy writes, and the decode dispatch carries both to
        # the device as its arguments
        self.pos = np.zeros((self.batch_size,), np.int32)
        self.tokens = np.zeros((self.batch_size, 1), np.int32)
        # force_token calls since the last step (the ``forced`` arg of the
        # ``engine.step`` span)
        self._forced = 0
        self.active = np.zeros((self.batch_size,), bool)
        # slots a numeric watchdog pulled out of service (see serving.guards):
        # quarantined slots refuse admission until clear_quarantine() runs
        self.quarantined = np.zeros((self.batch_size,), bool)
        # decode-step logits of the last step() (host copy, (batch, vocab)) —
        # what the numeric watchdog inspects for NaN/Inf/overflow
        self.last_logits: np.ndarray | None = None

        if self.mesh is not None:
            from repro.launch.steps import wire_serve_cell

            cell = wire_serve_cell(
                self.cfg, self.params, self.mesh,
                batch_size=self.batch_size, max_seq=self.max_seq,
                knobs=self.knobs, rules=self.rules,
            )
            self.params = cell.params
            self.rules = cell.rules
            self.pair_report = cell.pair_report
            self.cache = jax.tree.map(jax.device_put, self.cache, cell.c_shard)
            self._cell = cell
            self._decode = lambda p, c, t, pos: cell.decode(
                p, c, {"tokens": t, "pos": pos}
            )
            self._prefill = cell.prefill
            return

        # gemm == "pallas_paired" needs per-weight pairing metadata
        # (core.transform.pair_lm_params) next to the decoder weights.  If
        # the caller hasn't preprocessed the params already, run the paper's
        # one-time preprocessing here — knobs.pair_rounding sets the rounding
        # size, knobs.pair_block_n the pairing-spectrum point (0 →
        # structured shared-row pairing, n ≥ 1 → column-blocked, 1 == the
        # paper's per-column pairing).  The weights themselves stay live
        # (magnitudes recompute inside the traced step).
        self.pair_report = None
        if self.knobs.gemm == "pallas_paired":
            from repro.core.transform import has_lm_pairing, pair_lm_params
            from repro.kernels.ops import paired_mode_of

            if not has_lm_pairing(self.params):
                mode, block_n = paired_mode_of(self.knobs)
                self.params, self.pair_report = pair_lm_params(
                    self.params, self.knobs.pair_rounding,
                    mode=mode, block_n=block_n,
                )
                # the metadata comes back as host arrays: place it once,
                # not on every step call
                self.params = jax.device_put(self.params)

        # knobs.gemm == "pallas" routes every layers.dense GEMM in the traced
        # step through the fused K-tiled kernel ("pallas_paired" routes the
        # pairing-annotated decoder GEMMs through the subtractor kernel, with
        # the sublayer residual adds fused into its epilogue), knobs.conv
        # selects the conv
        # lowering for conv-bearing models (knobs.fuse_pool additionally
        # fuses 2×2 pooling into the conv epilogue, knobs.pair_block_n the
        # pairing-mode spectrum point the conv artifacts use), and
        # knobs.tile_cache points tile selection at persisted measured
        # winners (the policies are consulted at trace time, so they must
        # wrap the function body, not the jit call).  Their names are the
        # names of the programs in a profiler trace (``jit_serve_decode``).
        def serve_decode(p, c, t, pos):
            self.counters.traces += 1
            with perf_context(self.knobs):
                return M.decode_step(self.cfg, p, c, t, pos)

        def serve_prefill(p, b):
            self.counters.traces += 1
            with perf_context(self.knobs):
                return M.prefill(self.cfg, p, b, knobs=self.knobs)

        self._decode = jax.jit(serve_decode)
        self._prefill = jax.jit(serve_prefill)

    # -- request management -------------------------------------------------
    def add_request(self, slot: int, prompt: np.ndarray, extras: dict | None = None):
        """Prefill a prompt into one slot. prompt: (plen,) int32.

        Admission is validated, not asserted: ``assert`` vanishes under
        ``python -O`` and JAX scatter would then clamp an oversized prompt's
        cache writes into the last row silently.  Raises :class:`CapacityError`
        on any bound violation; a quarantined slot refuses admission until
        :meth:`clear_quarantine`.
        """
        plen = len(prompt)
        if not 0 <= slot < self.batch_size:
            raise CapacityError(
                f"slot {slot} out of range for batch_size={self.batch_size}")
        if self.active[slot]:
            raise CapacityError(
                f"slot {slot} is still active — release_slot() it first")
        if self.quarantined[slot]:
            raise CapacityError(
                f"slot {slot} is quarantined — clear_quarantine() it first")
        if plen < 1:
            raise CapacityError("empty prompt")
        if plen >= self.max_seq:
            raise CapacityError(
                f"prompt length {plen} leaves no decode room in "
                f"max_seq={self.max_seq} (need plen < max_seq)")
        # splice this request's prefill cache into the engine cache at `slot`
        def splice(dst_seg, src_seg):
            out = {}
            for k, dst in dst_seg.items():
                src = src_seg[k].astype(dst.dtype)
                if k == "h":  # ssm state: no seq axis
                    out[k] = dst.at[:, slot].set(src[:, 0])
                elif k.startswith("conv"):
                    out[k] = dst.at[:, slot].set(src[:, 0])
                elif k in ("xk", "xv"):  # cross-attn K/V: full frames axis
                    out[k] = dst.at[:, slot].set(src[:, 0])
                else:  # attention K/V or MLA latents: seq axis at dim 2
                    L = src.shape[2]
                    out[k] = dst.at[:, slot, :L].set(src[:, 0])
            return out

        req = self.counters.admissions
        with span("engine.admit", slot=slot, req=req, prompt_tokens=plen):
            batch = {"tokens": jnp.asarray(prompt[None, :], jnp.int32)}
            batch.update(extras or {})
            with span("engine.admit.prefill") as sp:
                last_logits, cache = self._prefill(self.params, batch)
                sp.set_metadata(traces=self.counters.traces)

            with span("engine.admit.splice"):
                self.cache = {
                    "segments": [
                        splice(d, s)
                        for d, s in zip(self.cache["segments"], cache["segments"], strict=True)
                    ]
                }
                self.pos[slot] = plen
            with span("engine.admit.first_token") as sp:
                first = jnp.argmax(last_logits[0, -1, : self.cfg.vocab])
                next_tok = int(first)
                sp.set_metadata(bytes=first.nbytes)
            self.counters.d2h_bytes += first.nbytes
            self.tokens[slot, 0] = next_tok
            self.active[slot] = True
            self.slot_req[slot] = req
            self.counters.admissions += 1
        return next_tok

    def prefill_logits(self, prompt: np.ndarray) -> np.ndarray:
        """(vocab,) next-token logits after prefilling ``prompt`` — the
        prefill :meth:`add_request` runs, without touching any slot (a
        parity probe on the engine's own compiled step)."""
        batch = {"tokens": jnp.asarray(prompt[None, :], jnp.int32)}
        last_logits, _ = self._prefill(self.params, batch)
        out = np.asarray(last_logits[0, -1, : self.cfg.vocab])
        self.counters.d2h_bytes += out.nbytes
        return out

    def step(self, sample: Callable | None = None) -> np.ndarray:
        """One decode step for every active slot. Returns (batch,) next tokens.

        Inactive slots emit :data:`INACTIVE_TOKEN` (-1) — finished or evicted
        sequences stop producing model output.  Raises :class:`CapacityError`
        when any *active* slot has no cache row left (``pos >= max_seq``)
        instead of letting the scatter clamp into the last row.

        The slots' input tokens and positions are host arrays: the capacity
        check reads no device value, the decode dispatch takes a snapshot of
        both as its arguments, and the step's one host read of its tokens
        becomes the next step's inputs.
        """
        c = self.counters
        with span("engine.step", step=c.steps, active=int(self.active.sum()),
                  forced=self._forced) as st:
            over = self.active & (self.pos >= self.max_seq)
            if over.any():
                raise CapacityError(
                    f"slot(s) {np.flatnonzero(over).tolist()} at pos "
                    f"{self.pos[over].tolist()} have no cache rows "
                    f"left (max_seq={self.max_seq}) — evict or raise max_seq")
            self._forced = 0
            with span("engine.step.dispatch") as sp:
                # copies: a backend may alias a numpy argument's buffer, and
                # both arrays are written again before the step has run
                logits, self.cache = self._decode(
                    self.params, self.cache, self.tokens.copy(), self.pos.copy())
                sp.set_metadata(traces=c.traces)
            with span("engine.step.sample"):
                logits = logits[:, 0, : self.cfg.vocab]
                nxt = (jnp.argmax(logits, axis=-1).astype(jnp.int32) if sample is None
                       else sample(logits))
            with span("engine.step.logits_to_host", bytes=logits.nbytes):
                self.last_logits = np.asarray(logits)
            with span("engine.step.tokens_to_host", bytes=nxt.nbytes):
                nxt = np.asarray(nxt)
            self.tokens = nxt.astype(np.int32).reshape(self.batch_size, 1)
            self.pos += self.active
            moved = self.last_logits.nbytes + nxt.nbytes
            st.set_metadata(d2h_bytes=moved)
        c.steps += 1
        c.d2h_bytes += moved
        return np.where(self.active, nxt, INACTIVE_TOKEN)

    def force_token(self, slot: int, token: int) -> None:
        """Override the next input token for one slot (chunked prefill:
        the front end feeds the unprefilled tail of a long prompt through
        the shared decode steps, one token per step, so other slots keep
        decoding instead of stalling behind a monolithic prefill).

        A host write into the slots' input tokens: no device op, no
        transfer; the next :meth:`step` carries it to the device."""
        with span("engine.force", slot=slot, req=self.slot_req[slot]):
            self.tokens[slot, 0] = token
        self.counters.forced_tokens += 1
        self._forced += 1

    def release_slot(self, slot: int, *, scrub: bool = True) -> None:
        """Evict a slot: mark it free and (by default) scrub its cache rows.

        Scrubbing zeroes every cache entry's ``slot`` row (K/V, MLA latents,
        SSM state, conv state) so a later request admitted into the slot can
        never attend stale keys from the previous occupant.
        """
        if not 0 <= slot < self.batch_size:
            raise CapacityError(
                f"slot {slot} out of range for batch_size={self.batch_size}")
        with span("engine.release", slot=slot, req=self.slot_req[slot]):
            self.active[slot] = False
            self.pos[slot] = 0
            self.tokens[slot, 0] = 0
            if scrub:
                with span("engine.release.scrub"):
                    self.cache = {
                        "segments": [
                            {k: v.at[:, slot].set(0) for k, v in seg.items()}
                            for seg in self.cache["segments"]
                        ]
                    }
        self.counters.releases += 1

    def quarantine_slot(self, slot: int) -> None:
        """Pull a slot out of service: evict + scrub + refuse admission until
        :meth:`clear_quarantine`.  The numeric watchdog (serving.guards) calls
        this when the slot's logits go non-finite; the request itself is the
        front end's to retry on the degraded path."""
        self.release_slot(slot, scrub=True)
        self.quarantined[slot] = True

    def clear_quarantine(self, slot: int) -> None:
        self.quarantined[slot] = False

    def free_slots(self) -> list[int]:
        """Slots admission may use right now (inactive and not quarantined)."""
        return [
            i for i in range(self.batch_size)
            if not self.active[i] and not self.quarantined[i]
        ]

    def generate(self, slot_prompts: dict[int, np.ndarray], n_steps: int,
                 extras: dict | None = None) -> dict[int, list[int]]:
        """Convenience: prefill the given slots, decode n_steps greedily."""
        outs: dict[int, list[int]] = {}
        for slot, prompt in slot_prompts.items():
            first = self.add_request(slot, prompt, extras)
            outs[slot] = [first]
        for _ in range(n_steps - 1):
            nxt = self.step()
            for slot in slot_prompts:
                outs[slot].append(int(nxt[slot]))
        return outs
