"""The program's own spans, programs and scopes in a traced run's profile.

``bench/trace_reduce.py`` reads the trace from outside the program: the
client's spans and the device ops by instruction name.  This module reads
what the program writes into the same ``.xplane.pb``:

* the serving engine's host spans (``engine.*``, ``repro.serving.trace``)
  with their args, nested by time;
* the device's ``XLA Modules`` events: one per run of a compiled program,
  named after the jitted function (``jit_serve_decode(<program id>)``);
* each ``XLA Ops`` event's scope path: the ``tf_op`` stat of its event
  metadata (``jit(serve_decode)/layer_scan/while/body/closed_call/mlp/refold/
  jit(_take)/gather:``), which carries the program's ``jax.named_scope``s.

``jax.profiler.ProfileData`` gives events but not their metadata's stats, so
the file is decoded here from the protobuf wire format of ``XSpace``
(``tsl/profiler/protobuf/xplane.proto``) with nothing but the standard
library.  A traced run reads its file once for all the metrics that use it
(:func:`for_run`, cached on path and modification time) and logs on stderr
the device self time of each program by scope and the device idle time by
the innermost engine span open over it.

A program without these spans, programs or scopes (an older checkout) reads
as an empty trace of them: every metric that needs them returns nothing.
"""
from __future__ import annotations

import dataclasses
import functools
import struct
import sys
import time
from bisect import bisect_left, bisect_right
from collections import defaultdict
from pathlib import Path

from bench.trace_reduce import _union, op_name

#: where ``bench/run.py`` writes a traced run's profile: ``<SCRATCH>/<workload>``
SCRATCH = Path(__file__).resolve().parent.parent / ".cache" / "bench" / "trace"
SPAN_PREFIX = "engine."
DEVICE_PLANE = "/device:TPU:0"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
#: the serving engine's jitted steps, as ``XLA Modules`` names them (no ``jit_``)
DECODE = "serve_decode"
PREFILL = "serve_prefill"
#: the program's named scopes; an op belongs to the innermost one on its path
SCOPES = ("embed", "attn", "ssm", "mlp", "cache_update", "head", "refold", "layer_scan")
UNSCOPED = "(none)"


# -- protobuf wire format ------------------------------------------------------

def _varint(buf: bytes, i: int) -> tuple[int, int]:
    b = buf[i]
    if b < 0x80:
        return b, i + 1
    out, shift = b & 0x7F, 7
    while True:
        i += 1
        b = buf[i]
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i + 1
        shift += 7


def _fields(buf: bytes, i: int, end: int):
    """(field number, wire type, value) of a message in ``buf[i:end]``; a
    length-delimited value is its (start, end) in ``buf``."""
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire == 1:
            v, i = struct.unpack_from("<d", buf, i)[0], i + 8
        elif wire == 5:
            v, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, wire, v


def _str(buf: bytes, span: tuple[int, int]) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _map(buf: bytes, span: tuple[int, int]) -> tuple[int, tuple[int, int] | None]:
    """One ``map<int64, Message>`` entry: (key, the value's span)."""
    key, val = 0, None
    for f, _, v in _fields(buf, *span):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


def _stat(buf: bytes, span: tuple[int, int], stat_names: dict) -> tuple[str, object]:
    """An ``XStat``: (its name, its value); a ``ref_value`` names another
    stat's metadata, whose name is the value."""
    mid, val = 0, None
    for f, _, v in _fields(buf, *span):
        if f == 1:
            mid = v
        elif f == 4:
            val = _signed(v)
        elif f in (2, 3):
            val = v
        elif f in (5, 6):
            val = _str(buf, v)
        elif f == 7:
            val = stat_names.get(v, "")
    return stat_names.get(mid, ""), val


def _metadata(buf: bytes, span: tuple[int, int], stat_names: dict) -> tuple[str, dict]:
    """An ``XEventMetadata``: (its name, its stats)."""
    name, stats = "", {}
    for f, _, v in _fields(buf, *span):
        if f == 2:
            name = _str(buf, v)
        elif f == 5:
            k, val = _stat(buf, v, stat_names)
            stats[k] = val
    return name, stats


def _line(buf: bytes, span: tuple[int, int], keep=None, stat_names=None):
    """An ``XLine``: (its name, its events as (start ps, end ps, metadata id,
    stats)).  ``keep`` limits the events to those metadata ids, whose stats
    (with ``stat_names``) are decoded; the others' are skipped."""
    name, ts_ns, raw = "", 0, []
    for f, _, v in _fields(buf, *span):
        if f == 2:
            name = _str(buf, v)
        elif f == 3:
            ts_ns = v
        elif f == 4:
            raw.append(v)
    events = []
    base = ts_ns * 1000
    for a, b in raw:
        mid = off = dur = 0
        stats = None
        for f, _, v in _fields(buf, a, b):
            if f == 1:
                mid = v
                if keep is not None and mid not in keep:
                    break
            elif f == 2:
                off = v
            elif f == 3:
                dur = v
            elif f == 4 and keep is not None:
                k, val = _stat(buf, v, stat_names)
                stats = stats or {}
                stats[k] = val
        else:
            events.append((base + off, base + off + dur, mid, stats))
    return name, events


def _planes(buf: bytes):
    """(name, span) of each ``XPlane`` of an ``XSpace``."""
    for f, _, v in _fields(buf, 0, len(buf)):
        if f == 1:
            for g, _, w in _fields(buf, *v):
                if g == 2:
                    yield _str(buf, w), v
                    break


def _plane_parts(buf: bytes, span: tuple[int, int]):
    """(line spans, event metadata spans by id, stat names by id) of a plane."""
    lines, events, stat_names = [], {}, {}
    for f, _, v in _fields(buf, *span):
        if f == 3:
            lines.append(v)
        elif f == 4:
            k, val = _map(buf, v)
            if val is not None:
                events[k] = val
        elif f == 5:
            k, val = _map(buf, v)
            if val is not None:
                for g, _, w in _fields(buf, *val):
                    if g == 2:
                        stat_names[k] = _str(buf, w)
    return lines, events, stat_names


# -- the reduction -------------------------------------------------------------

@dataclasses.dataclass
class Span:
    name: str
    start: int  # ps, the profiler's clock
    end: int
    args: dict
    depth: int = 0  # engine spans open around it


@dataclasses.dataclass(slots=True)
class Op:
    start: int
    end: int
    self_ps: int  # duration less that of the ops nested in it
    instruction: int  # its event metadata id: one HLO instruction
    name: str  # the instruction's name without its number (``paired_matmul``)
    program: str  # ``serve_decode``, ``argmax``, ... ("" when unknown)
    path: str  # scope path without the op type (``tf_op`` up to its last ':')
    scope: str  # the innermost of ``SCOPES`` on the path, else ``UNSCOPED``


@dataclasses.dataclass
class ProgramTrace:
    spans: list[Span]  # engine spans, by start
    modules: list[tuple[str, int, int]]  # (program, start ps, end ps), by start
    ops: list[Op]  # device ops, by start
    busy: list[tuple[int, int]]  # union of the ops' intervals
    window: tuple[int, int]
    read_s: float = 0.0

    def named(self, name: str) -> list[Span]:
        return [s for s in self.spans if s.name == name]

    def module_runs(self, program: str) -> list[tuple[int, int]]:
        return [(a, b) for p, a, b in self.modules if p == program]

    def self_seconds(self, pred) -> float:
        return sum(o.self_ps for o in self.ops if pred(o)) * 1e-12

    def path_stats(self, path: str) -> tuple[int, float]:
        """(occurrences, self seconds) of the ops with this scope path, as
        profiler tools count them: occurrences are the runs of its most
        frequent instruction (one per loop trip), not its events."""
        runs: dict[int, int] = defaultdict(int)
        ps = 0
        for o in self.ops:
            if o.path == path:
                runs[o.instruction] += 1
                ps += o.self_ps
        return max(runs.values(), default=0), ps * 1e-12

    def busy_within(self, a: int, b: int) -> int:
        """Picoseconds of [a, b) in which some op ran."""
        i = max(bisect_right(self.busy, (a, a)) - 1, 0)
        t = 0
        for s, e in self.busy[i:]:
            if s >= b:
                break
            t += max(0, min(b, e) - max(a, s))
        return t

    def idle_seconds(self, spans: list[Span]) -> float:
        """Device idle time inside the spans (which must not overlap)."""
        return sum((s.end - s.start) - self.busy_within(s.start, s.end) for s in spans) * 1e-12

    def idle_by_span(self) -> dict[str, float]:
        """Idle seconds of the window by the innermost engine span open over
        them (``UNSCOPED``: none open)."""
        gaps, prev = [], self.window[0]
        for a, b in self.busy:
            if a > prev:
                gaps.append((prev, a))
            prev = max(prev, b)
        if prev < self.window[1]:
            gaps.append((prev, self.window[1]))
        # cut points where the innermost span can change
        cuts = sorted({t for s in self.spans for t in (s.start, s.end)})
        starts = [s.start for s in self.spans]
        out: dict[str, float] = defaultdict(float)
        for a, b in gaps:
            pts = [a] + cuts[bisect_right(cuts, a):bisect_left(cuts, b)] + [b]
            for lo, hi in zip(pts, pts[1:], strict=False):
                out[self._innermost(lo, bisect_right(starts, lo))] += (hi - lo) * 1e-12
        return dict(out)

    def _innermost(self, t: int, k: int) -> str:
        """Name of the deepest span open at ``t`` among the first ``k``
        (those started by ``t``): it lies after the last top-level one."""
        best = None
        for i in range(k - 1, -1, -1):
            s = self.spans[i]
            if s.end > t and (best is None or s.depth > best.depth):
                best = s
            if s.depth == 0:
                break
        return best.name if best else UNSCOPED


def _nest(events):
    """Self time of each of ``events`` (start, end, ...) sorted by start: its
    duration less the durations of the events directly inside it."""
    self_ps = [e[1] - e[0] for e in events]
    stack: list[int] = []
    for i, e in enumerate(events):
        while stack and events[stack[-1]][1] <= e[0]:
            stack.pop()
        if stack:
            self_ps[stack[-1]] -= e[1] - e[0]
        stack.append(i)
    return self_ps


def _program(module_name: str) -> str:
    """``jit_serve_decode(8407...)`` -> ``serve_decode``."""
    name = module_name.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


def _name(buf: bytes, span: tuple[int, int]) -> str:
    """The ``name`` of an ``XEventMetadata``, its other fields left unread."""
    for f, _, v in _fields(buf, *span):
        if f == 2:
            return _str(buf, v)
    return ""


def reduce_xspace(buf: bytes) -> ProgramTrace:
    """The engine spans, program runs and device ops of a serialized ``XSpace``."""
    spans: list[Span] = []
    modules: list[tuple[str, int, int]] = []
    raw_ops: list[tuple] = []
    meta: dict[int, tuple[str, dict]] = {}
    for name, span in _planes(buf):
        if name == DEVICE_PLANE:
            lines, events, stat_names = _plane_parts(buf, span)
            meta = {k: _metadata(buf, v, stat_names) for k, v in events.items()}
            for ln in lines:
                lname, evs = _line(buf, ln)
                if lname == MODULES_LINE:
                    modules = [(meta.get(m, ("", {}))[0], a, b) for a, b, m, _ in evs]
                elif lname == OPS_LINE:
                    raw_ops = evs
        elif name.startswith("/host:"):
            lines, events, stat_names = _plane_parts(buf, span)
            keep = {k: n for k, n in ((k, _name(buf, v)) for k, v in events.items())
                    if n.startswith(SPAN_PREFIX)}
            if keep:
                for ln in lines:
                    for a, b, m, st in _line(buf, ln, keep, stat_names)[1]:
                        spans.append(Span(keep[m], a, b, st or {}))
    # an op's program from its metadata's program id, named by the module
    # events (``jit_serve_decode(<id>)``), else from its scope path
    programs = {}
    for mname, _, _ in modules:
        pid = mname.partition("(")[2].rstrip(")")
        if pid.isdigit():
            programs[int(pid)] = _program(mname)
    kinds = {}  # metadata id -> (name, program, path, scope), shared by its events
    for m, (mname, st) in meta.items():
        path = str(st.get("tf_op", "")).rsplit(":", 1)[0]
        prog = programs.get(st.get("program_id"), "")
        if not prog and path.startswith("jit("):
            prog = path[4:path.find(")")]
        scope = next((p for p in reversed(path.split("/")) if p in SCOPES), UNSCOPED)
        kinds[m] = (op_name(mname), prog, path, scope)
    modules = sorted(((_program(n), a, b) for n, a, b in modules), key=lambda m: m[1])
    raw_ops.sort(key=lambda e: (e[0], -e[1]))
    unknown = ("", "", "", UNSCOPED)
    ops = [Op(a, b, sp, m, *kinds.get(m, unknown))
           for (a, b, m, _), sp in zip(raw_ops, _nest(raw_ops), strict=True)]
    # the engine's spans come from the thread that calls it: nest them by time
    spans.sort(key=lambda s: (s.start, -s.end))
    stack: list[Span] = []
    for s in spans:
        while stack and stack[-1].end <= s.start:
            stack.pop()
        s.depth = len(stack)
        stack.append(s)
    busy = _union([(o.start, o.end) for o in ops])
    ends = [t for s in spans for t in (s.start, s.end)] + [t for iv in busy for t in iv]
    window = (min(ends), max(ends)) if ends else (0, 0)
    return ProgramTrace(spans, modules, ops, busy, window)


@functools.lru_cache(maxsize=2)
def _load(path: str, mtime_ns: int) -> ProgramTrace:
    t = time.perf_counter()
    trace = reduce_xspace(Path(path).read_bytes())
    trace.read_s = time.perf_counter() - t
    _log(path, trace)
    return trace


def load(path) -> ProgramTrace:
    p = Path(path)
    return _load(str(p), p.stat().st_mtime_ns)


def trace_path(run) -> Path | None:
    """The traced run's profile: ``run.trace_path`` where the run names one,
    else the newest ``.xplane.pb`` where ``bench/run.py`` writes it."""
    given = getattr(run, "trace_path", None)
    if given is not None:
        return Path(given)
    found = sorted((SCRATCH / run.workload).rglob("*.xplane.pb"))
    return found[-1] if found else None


def for_run(run) -> ProgramTrace | None:
    """The run's program trace, or nothing for an untraced run."""
    if getattr(run, "trace", None) is None:
        return None
    path = trace_path(run)
    return load(path) if path is not None and path.exists() else None


def decode_steps(run) -> tuple[ProgramTrace, int] | None:
    """(trace, steps) where the trace holds one ``serve_decode`` run for each
    of the window's steps; else nothing."""
    trace = for_run(run)
    if trace is None or not run.steps:
        return None
    n = len(trace.module_runs(DECODE))
    if n != len(run.steps):
        print(f"[bench] program_trace: {n} {DECODE} runs for {len(run.steps)} steps; no reading",
              file=sys.stderr)
        return None
    return trace, n


def _log(path: str, trace: ProgramTrace) -> None:
    def say(msg):
        print(f"[bench] program_trace: {msg}", file=sys.stderr, flush=True)

    say(f"read {Path(path).stat().st_size / 1e6:.1f} MB in {trace.read_s:.2f}s: "
        f"{len(trace.spans)} engine spans, {len(trace.modules)} program runs, "
        f"{len(trace.ops)} device ops")
    by_prog: dict[str, dict[str, int]] = defaultdict(lambda: defaultdict(int))
    by_op: dict[str, dict[str, int]] = defaultdict(lambda: defaultdict(int))
    for o in trace.ops:
        by_prog[o.program or UNSCOPED][o.scope] += o.self_ps
        if o.program == DECODE:
            by_op[o.scope][o.name] += o.self_ps
    for prog, scopes in sorted(by_prog.items(), key=lambda kv: -sum(kv[1].values())):
        total = sum(scopes.values())
        parts = ", ".join(f"{k} {v * 1e-12:.4f}s ({100 * v / total:.1f}%)"
                          for k, v in sorted(scopes.items(), key=lambda kv: -kv[1]))
        say(f"device self time of {prog}: {total * 1e-12:.4f}s; by scope: {parts}")
    for scope, names in sorted(by_op.items(), key=lambda kv: -sum(kv[1].values())):
        top = sorted(names.items(), key=lambda kv: -kv[1])[:5]
        say(f"{DECODE} {scope}, top ops: " + ", ".join(f"{k} {v * 1e-12:.4f}s" for k, v in top))
    idle = sorted(trace.idle_by_span().items(), key=lambda kv: -kv[1])
    say("device idle by innermost engine span: "
        + ", ".join(f"{k} {v:.4f}s" for k, v in idle))
