"""Reduction of a profiler trace to the benchmark's device numbers.

A traced run records the measured window under ``jax.profiler`` with the
harness's own host spans (``bench.*``) and the program's stage spans
(``stage.*``) as ``TraceAnnotation``s.  This module reads the
``.xplane.pb`` file with ``jax.profiler.ProfileData`` alone and reduces it:

- device busy time: the union of the intervals in which an operation
  ran on a device, inside the window, averaged over the devices used;
- idle gaps: the stretches of the window with no device operation, each
  named by the innermost host span that covers its middle;
- kernel time: summed device durations of the events whose name holds a
  given substring;
- the bytes an operation must move: each distinct operand read once and
  each result written once, from the shapes in its HLO text
  (:func:`hlo_bytes`).

The pure functions below take plain event lists, so they are tested on
hand-built lists as well as on a recorded trace.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
# lines of a TPU device plane that hold one event per executed operation
OP_LINES = ("XLA Ops",)


_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
          "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
          "f64": 8}
_SHAPE = re.compile(r"\b(" + "|".join(_BYTES) + r")\[([\d,]*)\]")
_OPERAND = re.compile(_SHAPE.pattern + r"(?:\{[^}]*\})?\s+(%[\w.\-]+)")


@dataclass
class Event:
    name: str
    start_ns: float
    end_ns: float


def op_name(text: str) -> str:
    """An XLA op event's instruction name: a TPU trace names each op by
    its whole HLO text, ``%fusion.14 = (u32[64]...) fusion(...)``."""
    return text.split(" = ", 1)[0].lstrip("%")


def _closing(text: str, i: int) -> int:
    """Index of the parenthesis that closes the one at ``text[i]``."""
    depth = 0
    for j in range(i, len(text)):
        depth += {"(": 1, ")": -1}.get(text[j], 0)
        if depth == 0:
            return j
    raise ValueError(f"unbalanced HLO text: {text[:80]!r}")


def _nbytes(dtype: str, dims: str) -> int:
    n = _BYTES[dtype]
    for d in filter(None, dims.split(",")):
        n *= int(d)
    return n


def hlo_bytes(text: str) -> int:
    """Bytes an HLO instruction must move: its results written once and
    each distinct operand read once, from the shapes in its text
    (``%x = (s32[..]{..}, ..) custom-call(s32[..]{..} %a, ..), ...``)."""
    rhs = text.split(" = ", 1)[1]
    if rhs.startswith("("):
        end = _closing(rhs, 0)
        result, rest = rhs[:end + 1], rhs[end + 1:]
    else:
        result, rest = rhs.split(" ", 1)
    start = rest.index("(")
    operands = rest[start:_closing(rest, start) + 1]
    written = sum(_nbytes(t, d) for t, d in _SHAPE.findall(result))
    read = {name: _nbytes(t, d) for t, d, name in _OPERAND.findall(operands)}
    return written + sum(read.values())


@dataclass
class Trace:
    """Events of one traced window, split into device ops and host spans."""

    device_ops: Dict[str, List[Event]] = field(default_factory=dict)
    host_spans: List[Event] = field(default_factory=list)
    window: Optional[Tuple[float, float]] = None
    hlo: Dict[str, str] = field(default_factory=dict)   # op name -> text

    @property
    def window_s(self) -> float:
        lo, hi = self.window
        return (hi - lo) * 1e-9

    def busy_s(self) -> float:
        """Device-busy seconds in the window, averaged over devices."""
        if not self.device_ops:
            return 0.0
        lo, hi = self.window
        tot = sum(union_ns([(e.start_ns, e.end_ns) for e in evs], lo, hi)
                  for evs in self.device_ops.values())
        return tot * 1e-9 / len(self.device_ops)

    def idle_share(self) -> Optional[float]:
        if not self.device_ops or self.window_s <= 0:
            return None
        return 1.0 - self.busy_s() / self.window_s

    def kernel_events(self, substring: str) -> List[Tuple[str, float]]:
        """[(op name, seconds in the window), ...] of the events on every
        device whose name holds ``substring``."""
        lo, hi = self.window
        return [(e.name, (min(e.end_ns, hi) - max(e.start_ns, lo)) * 1e-9)
                for evs in self.device_ops.values() for e in evs
                if substring in e.name and e.end_ns > lo and e.start_ns < hi]

    def kernel_s(self, substring: str) -> float:
        """Summed device seconds of events whose name holds ``substring``,
        averaged over devices."""
        if not self.device_ops:
            return 0.0
        return sum(s for _, s in self.kernel_events(substring)) \
            / len(self.device_ops)

    def top_ops(self, n: int = 10) -> List[List]:
        """[[name, seconds], ...] of the device ops that took most time."""
        lo, hi = self.window
        acc: Dict[str, float] = {}
        for evs in self.device_ops.values():
            for e in evs:
                if e.end_ns > lo and e.start_ns < hi:
                    d = min(e.end_ns, hi) - max(e.start_ns, lo)
                    acc[e.name] = acc.get(e.name, 0.0) + d * 1e-9
        k = max(len(self.device_ops), 1)
        rows = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[name, s / k] for name, s in rows]

    def idle_gaps(self, n: int = 10) -> List[List]:
        """[[host span, seconds], ...]: the longest idle gaps of the first
        device, each named by what the host was doing in it."""
        if not self.device_ops:
            return []
        lo, hi = self.window
        dev = sorted(self.device_ops)[0]
        gaps = gaps_ns([(e.start_ns, e.end_ns)
                        for e in self.device_ops[dev]], lo, hi)
        gaps.sort(key=lambda g: -(g[1] - g[0]))
        return [[attribute(g, self.host_spans), (g[1] - g[0]) * 1e-9]
                for g in gaps[:n]]


def union_ns(intervals: Iterable[Tuple[float, float]], lo: float,
             hi: float) -> float:
    """Length of the union of ``intervals`` clipped to [lo, hi]."""
    tot, cur_s, cur_e = 0.0, None, None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                tot += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        tot += cur_e - cur_s
    return tot


def gaps_ns(intervals: Iterable[Tuple[float, float]], lo: float,
            hi: float) -> List[Tuple[float, float]]:
    """Stretches of [lo, hi] that no interval covers."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if e <= t:
            continue
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [g for g in out if g[1] > g[0]]


def attribute(gap: Tuple[float, float], spans: Sequence[Event]) -> str:
    """Name of the shortest host span covering the gap's middle."""
    mid = 0.5 * (gap[0] + gap[1])
    best = None
    for sp in spans:
        if sp.start_ns <= mid <= sp.end_ns and (
                best is None
                or sp.end_ns - sp.start_ns < best.end_ns - best.start_ns):
            best = sp
    return best.name if best is not None else "untraced"


def host_span_names(name: str) -> bool:
    return name.startswith("bench.") or name.startswith("stage.")


def from_events(device_ops: Dict[str, List[Event]],
                host_spans: List[Event],
                hlo: Optional[Dict[str, str]] = None) -> Trace:
    """Assemble a :class:`Trace`; the window is the ``bench.window`` span."""
    win = [s for s in host_spans if s.name == WINDOW_SPAN]
    if not win:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
    w = max(win, key=lambda s: s.end_ns - s.start_ns)
    return Trace(device_ops={k: v for k, v in device_ops.items() if v},
                 host_spans=list(host_spans),
                 window=(w.start_ns, w.end_ns), hlo=dict(hlo or {}))


def load(log_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under a ``jax.profiler`` log dir."""
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return load_file(max(paths, key=os.path.getmtime))


def load_file(path: str) -> Trace:
    """Reduce one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device_ops: Dict[str, List[Event]] = {}
    host: List[Event] = []
    hlo: Dict[str, str] = {}
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            evs = device_ops.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name in OP_LINES:
                    for e in line.events:
                        name = op_name(e.name)
                        hlo.setdefault(name, e.name)
                        evs.append(Event(name, e.start_ns, e.end_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(Event(e.name, e.start_ns, e.end_ns)
                            for e in line.events if host_span_names(e.name))
    return from_events(device_ops, host, hlo)
