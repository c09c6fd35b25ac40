"""From a profiler trace (``.xplane.pb``) to device busy/idle time, per-op
device time and the ``breakdown`` of a traced run.

Read with ``jax.profiler.ProfileData``.  A device plane is one named
``/device:TPU:<n>``; its ops are the events of its ``XLA Ops`` line, each
named by its HLO instruction (``%paired_matmul.72 = bf16[...] custom-call(...)``);
an op's name here is that instruction's name without the ``%`` and the
numeric suffix (``paired_matmul``; a Pallas kernel keeps its own).  The
host spans are the ``TraceAnnotation`` events the client writes (``admit``,
``step``, ``force``, ``release``, ``client``).  The traced window runs from
the first host span or device op, whichever starts first, to the end of the
last one (the device's clock may lead the host's by a fraction of a
millisecond, so a window cut at the first span would drop the first ops).
The profiler starts after the warm phase has synchronised with the device,
so every op in the trace belongs to the window.  Loops and calls
(``CONTAINERS``) count toward busy time but not as ops, since their events
span the ops of their bodies.
"""
from __future__ import annotations

import dataclasses
import re
from bisect import bisect_right
from collections import defaultdict
from pathlib import Path

SPANS = ("admit", "step", "force", "release", "client")
#: ops whose event spans the ops of their body: busy time, not op time
CONTAINERS = ("while", "conditional", "call")
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


def op_name(event_name: str) -> str:
    """``%paired_matmul.72 = bf16[16,256] custom-call(...)`` -> ``paired_matmul``."""
    return re.sub(r"\.\d+$", "", event_name.split(" = ", 1)[0].lstrip("%"))


def find_xplane(trace_dir) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float  # averaged over the chips
    ops: dict[str, list[float]]  # op name -> [count, seconds], all chips
    idle_by_span: dict[str, float]  # host span open during idle time -> seconds
    n_chips: int

    def op_seconds(self, prefix: str) -> tuple[int, float]:
        """Events and device seconds of ops whose name starts with ``prefix``."""
        n = t = 0.0
        for name, (c, s) in self.ops.items():
            if name.startswith(prefix):
                n += c
                t += s
        return int(n), t

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1][1])[:top]
        idle = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v[1]] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in idle]}


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def summarize(path, n_chips: int = 1) -> Summary:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(str(path)).planes, n_chips)


def reduce_planes(planes, n_chips: int = 1) -> Summary:
    spans: list[tuple[int, int, str]] = []
    devices: list[list[tuple[int, int, str]]] = []
    for plane in planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.append([(int(e.start_ns), int(e.end_ns), op_name(e.name))
                                    for e in line.events])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((int(e.start_ns), int(e.end_ns), e.name)
                             for e in line.events if e.name in SPANS)
    if not spans:
        raise ValueError("the trace holds no client spans")
    devices = devices[:n_chips]
    w0 = min([s[0] for s in spans] + [min(e[0] for e in ev) for ev in devices if ev])
    w1 = max([s[1] for s in spans] + [max(e[1] for e in ev) for ev in devices if ev])
    ops: dict[str, list[float]] = defaultdict(lambda: [0, 0.0])
    busy = 0.0
    idle: dict[str, float] = defaultdict(float)
    spans.sort()
    for events in devices:
        for a, b, name in events:
            if name not in CONTAINERS:
                ops[name][0] += 1
                ops[name][1] += (b - a) * 1e-9
        merged = _union([(a, b) for a, b, _ in events])
        busy += sum(b - a for a, b in merged) * 1e-9
        gaps, prev = [], w0
        for a, b in merged:
            if a > prev:
                gaps.append((prev, a))
            prev = max(prev, b)
        if prev < w1:
            gaps.append((prev, w1))
        ends = [s[1] for s in spans]
        for a, b in gaps:
            for label, seconds in _attribute(a, b, spans, ends):
                idle[label] += seconds
    n = max(len(devices), 1)
    return Summary(window_s=(w1 - w0) * 1e-9, busy_s=busy / n, ops=dict(ops),
                   idle_by_span=dict(idle), n_chips=len(devices))


def _attribute(a: int, b: int, spans: list[tuple[int, int, str]], ends: list[int]):
    """Split the idle interval [a, b) by the host span open in each part of
    it (the spans do not overlap: each covers one engine call or the
    client's own work, in turn); time under no span is the client's own loop."""
    covered = 0
    for i in range(bisect_right(ends, a), len(spans)):
        s0, s1, name = spans[i]
        if s0 >= b:
            break
        lo, hi = max(a, s0), min(b, s1)
        if hi > lo:
            covered += hi - lo
            yield name, (hi - lo) * 1e-9
    if b - a > covered:
        yield "loop", (b - a - covered) * 1e-9
