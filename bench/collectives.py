"""Collective time on each chip, from the device ops of a traced window.

A TPU trace names each op by its HLO instruction.  An asynchronous
collective shows as a ``<kind>-start`` op that issues it and a
``<kind>-done`` op that waits for it; the transfer is in flight from the
start of the one to the end of the other (the done's HLO text names its
start as its operand).  A synchronous collective is one op.  So a
chip's collective intervals are its start-to-done spans and its
synchronous collectives; what runs of the chip's other ops inside them
hides them, and the rest is exposed.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

from bench.tracing import Event, union_ns

KINDS = ("collective-permute", "all-reduce", "all-gather", "all-to-all",
         "reduce-scatter", "collective-broadcast")
_DONE_OPERAND = re.compile(r"-done\(.*?%([\w.\-]+)")


def is_collective(name: str) -> bool:
    return name.startswith(KINDS)


def intervals(events: List[Event], hlo: Dict[str, str]
              ) -> List[Tuple[float, float]]:
    """One chip's collective intervals: each done op from the start of
    the latest start op it waits for, and each synchronous collective."""
    out, started = [], {}
    for e in sorted(events, key=lambda e: e.start_ns):
        if not is_collective(e.name):
            continue
        base = e.name.split(".", 1)[0]
        if base.endswith("-start"):
            started[e.name] = e.start_ns
        elif base.endswith("-done"):
            m = _DONE_OPERAND.search(hlo.get(e.name, ""))
            start = e.name.replace("-done", "-start", 1) if m is None \
                else m.group(1)
            out.append((started.pop(start, e.start_ns), e.end_ns))
        else:
            out.append((e.start_ns, e.end_ns))
    # a start whose done fell outside the trace counts its own op
    out += [(s, s) for s in started.values()]
    return out


def seconds(run, exposed: bool) -> Optional[float]:
    """Collective seconds per diagram, averaged over the chips; with
    ``exposed``, only the part in which no other op ran on the chip.
    None without a device trace, a diagram or a collective op."""
    trace = run.trace
    n = int(run.window.get("attempted", 0))
    if trace is None or not trace.device_ops or n == 0:
        return None
    lo, hi = trace.window
    total, found = 0.0, False
    for evs in trace.device_ops.values():
        coll = intervals(evs, trace.hlo)
        found = found or bool(coll)
        if not exposed:
            total += union_ns(coll, lo, hi)
            continue
        other = [(e.start_ns, e.end_ns) for e in evs
                 if not is_collective(e.name)]
        total += union_ns(coll + other, lo, hi) - union_ns(other, lo, hi)
    if not found:
        return None
    return total * 1e-9 / len(trace.device_ops) / n
