"""The readers of the program's own spans, on hand-built event lists."""

from __future__ import annotations

import pytest

from bench import registry, tracing
from bench.run import Run
from bench.tests.conftest import ROOT
from bench.tracing import Event

SECONDS = {"gradient_kernel_wait_s": "stage.gradient.kernel",
           "gradient_d2h_s": "stage.gradient.d2h",
           "gradient_unpack_s": "stage.gradient.unpack",
           "gradient_scatter_s": "stage.gradient.scatter",
           "extract_edge_keys_s": "stage.extract_sort.edge_keys"}
ROUNDS = {"d0_rounds.volume": "stage.d0_round",
          "d1_rounds.volume": "stage.d1_round"}
SPAN = {**SECONDS, **ROUNDS}
READERS = tuple(SPAN)
STAGES = ("order", "gradient", "extract_sort", "d0", "d_top", "d1")


def _diagram(t0):
    """The program's spans of one diagram that starts at ``t0`` ns: each
    stage 1000 ns long, each sub-span 100 ns, three D0 rounds, two D_top
    rounds and four D1 rounds."""
    ev = [Event("bench.diagram", t0, t0 + 6000)]
    for i, st in enumerate(STAGES):
        ev.append(Event("stage." + st, t0 + 1000 * i, t0 + 1000 * (i + 1)))
    g = t0 + 1000
    for j, sub in enumerate(("h2d", "kernel", "d2h", "unpack", "scatter")):
        ev.append(Event("stage.gradient." + sub, g + 100 * j,
                        g + 100 * j + 100))
    x = t0 + 2000
    for j, sub in enumerate(("critical", "edge_keys", "rank")):
        ev.append(Event("stage.extract_sort." + sub, x + 200 * j,
                        x + 200 * j + 100))
    for base, k, name in ((3000, 3, "d0_round"), (4000, 2, "d0_round"),
                          (5000, 4, "d1_round")):
        ev += [Event("stage." + name, t0 + base + 100 * r,
                     t0 + base + 100 * r + 50) for r in range(k)]
    return ev


def _run(host, attempted=2, device_ops=None):
    window = [Event("bench.window", 0, 100_000)]
    trace = tracing.from_events(device_ops or {}, window + host)
    return Run(cell=None, seed=0, device_kind="cpu", n_devices=1,
               window={"attempted": attempted}, trace=trace)


def _read(name, run):
    return registry.metric_reader(ROOT, name).read(run)


def _two_diagrams():
    return _diagram(1000) + _diagram(20_000)


def _doubled(events):
    """What the harness's outside wrapper adds: a second span around
    every stage-level span, a little wider."""
    return events + [Event(e.name, e.start_ns - 5, e.end_ns + 5)
                     for e in events
                     if e.name in {"stage." + s for s in STAGES}]


@pytest.mark.parametrize("name", SECONDS)
def test_seconds_are_a_mean_per_diagram(name):
    # two diagrams of one 100 ns span each
    assert _read(name, _run(_two_diagrams())) == pytest.approx(100e-9)
    # the same spans over four attempted diagrams read half as much
    assert _read(name, _run(_two_diagrams(), attempted=4)) == \
        pytest.approx(50e-9)


def test_rounds_are_a_count_per_diagram():
    run = _run(_two_diagrams())
    assert _read("d0_rounds.volume", run) == 3
    assert _read("d1_rounds.volume", run) == 4


@pytest.mark.parametrize("name", READERS)
def test_doubled_stage_spans_change_nothing(name):
    plain = _read(name, _run(_two_diagrams()))
    assert plain is not None
    assert _read(name, _run(_doubled(_two_diagrams()))) == \
        pytest.approx(plain)


@pytest.mark.parametrize("name", READERS)
def test_a_doubled_span_of_one_name_counts_once(name):
    events = _two_diagrams()
    twins = [Event(e.name, e.start_ns, e.end_ns) for e in events
             if e.name == SPAN[name]]
    assert twins
    assert _read(name, _run(events + twins)) == \
        pytest.approx(_read(name, _run(events)))


def test_d0_rounds_under_d_top_are_not_counted():
    events = _two_diagrams()
    extra = [Event("stage.d0_round", 1000 + 4000 + 500 + 10 * i,
                   1000 + 4000 + 505 + 10 * i) for i in range(7)]
    assert _read("d0_rounds.volume", _run(events + extra)) == 3


def test_spans_outside_the_window_are_not_counted():
    late = _diagram(200_000)             # after the window closed
    run = _run(_two_diagrams() + late)
    assert _read("d1_rounds.volume", run) == 4
    assert _read("gradient_d2h_s", run) == pytest.approx(100e-9)


@pytest.mark.parametrize("name", READERS)
def test_none_when_the_program_has_no_such_spans(name):
    # a program that predates the spans: only the outside stage wrapper
    stages_only = [e for e in _doubled(_two_diagrams())
                   if e.name in {"stage." + s for s in STAGES}
                   or e.name.startswith("bench.")]
    assert _read(name, _run(stages_only)) is None
    assert _read(name, Run(cell=None, seed=0, device_kind="cpu",
                           n_devices=1, window={"attempted": 2})) is None


@pytest.mark.parametrize("name", READERS)
def test_none_without_diagrams(name):
    assert _read(name, _run(_two_diagrams(), attempted=0)) is None
