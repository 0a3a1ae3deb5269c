"""Per-diagram readings of the program's own spans in a traced window.

The program puts every ``repro.obs`` span on the profiler's timeline as
a ``stage.<name>`` host span: its stages, the gradient stage's parts
(``stage.gradient.h2d``, ``.kernel``, ``.d2h``, ``.unpack``,
``.scatter``), critical extraction's parts (``stage.extract_sort.
critical``, ``.edge_keys``, ``.rank``) and one span per pairing round
(``stage.d0_round``, also run by D_top; ``stage.d1_round``).

While ``run.stage_spans`` also wraps the stages from outside, each
stage-level span shows twice, one inside the other.  So a reading takes,
of the spans of one name, only those no other span of that name holds,
and counts diagrams from the window record (``attempted``), never from
stage spans.  A program without the spans reads None.
"""

from __future__ import annotations

from typing import List, Optional

from bench.tracing import Event


def spans(run, name: str) -> List[Event]:
    """The outermost ``name`` spans whose middle lies in the window."""
    if run.trace is None:
        return []
    lo, hi = run.trace.window
    found = sorted((s for s in run.trace.host_spans if s.name == name
                    and lo <= 0.5 * (s.start_ns + s.end_ns) <= hi),
                   key=lambda s: (s.start_ns, -s.end_ns))
    out: List[Event] = []
    for s in found:
        if not out or s.end_ns > out[-1].end_ns:
            out.append(s)
    return out


def _diagrams(run) -> int:
    return int(run.window.get("attempted", 0))


def seconds_per_diagram(run, name: str) -> Optional[float]:
    """Summed seconds of the ``name`` spans over the window's diagrams."""
    found, n = spans(run, name), _diagrams(run)
    if not found or n == 0:
        return None
    return sum(s.end_ns - s.start_ns for s in found) * 1e-9 / n


def count_per_diagram(run, name: str,
                      inside: Optional[str] = None) -> Optional[float]:
    """``name`` spans over the window's diagrams; with ``inside``, only
    those whose middle lies in an ``inside`` span."""
    found, n = spans(run, name), _diagrams(run)
    if not found or n == 0:
        return None
    if inside is not None:
        outer = spans(run, inside)
        found = [s for s in found
                 if any(o.start_ns <= 0.5 * (s.start_ns + s.end_ns)
                        <= o.end_ns for o in outer)]
    return len(found) / n
