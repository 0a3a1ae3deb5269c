"""The four-chip cell rehearsed on four CPU devices, and the collective
readers on hand-built device events."""

from __future__ import annotations

import json

import pytest

from bench import registry
from bench.tests.conftest import ROOT, run_cell
from bench.tracing import Event, from_events

CELL = "shard4-wavelet-128"
SPAN_METRICS = {"order_s", "gradient_s", "extract_sort_s", "d0_s", "d1_s",
                "gradient_kernel_wait_s", "gradient_d2h_s",
                "gradient_scatter_s", "extract_edge_keys_s",
                "d0_rounds.volume", "d1_rounds.volume",
                "window_compiles.volume"}


def test_shard4_rehearsal_traced(tiny_root, capsys):
    """The cell at 8^3 on four forced host devices: correct, four devices,
    and every reading that needs no device plane."""
    import jax
    assert len(jax.devices()) >= 4, "bench/conftest.py forces 4 devices"
    res = run_cell(tiny_root, CELL, capsys, trace=1)
    assert res["correct"] is True, res["compared"]
    assert res["device"]["count"] == 4
    assert SPAN_METRICS <= set(res["metrics"])
    assert res["metrics"]["window_compiles.volume"]["value"] == 0
    # a CPU trace has no device plane: the device readers are silent
    assert not {"collective_s.shard4", "collective_exposed_s.shard4",
                "lower_star_roofline"} & set(res["metrics"])


def test_cell_entries():
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = registry.cell(ROOT, CELL)
    assert cell.chips == 4
    assert cell.config["pipeline"] == {"backend": "shardmap", "n_blocks": 4}
    names = {m["name"] for m in cell.per_layer}
    assert {"collective_s.shard4", "collective_exposed_s.shard4"} <= names
    assert "gradient_unpack_s" not in names
    assert CELL in next(m for m in bm["end_to_end"]
                        if m["name"] == "exact_vertices_per_s")["workloads"]


class _Run:
    def __init__(self, trace, diagrams):
        self.trace = trace
        self.window = {"attempted": diagrams}


def _trace(chips):
    """Chip 0: an all-reduce that nothing hides (100 ns), an all-gather
    that a fusion hides whole (100 ns), and a collective-permute in
    flight from its start to its done (200 ns) of which a fusion hides
    half.  Chip 1: only the exposed all-reduce."""
    hlo = {"collective-permute-done.1":
           "%collective-permute-done.1 = s32[4]{0} collective-permute-done("
           "(s32[4]{0}, s32[4]{0}, u32[], u32[]) %collective-permute-start.1)"}
    chip0 = [Event("all-reduce.1", 0, 100),
             Event("all-gather.1", 200, 300), Event("fusion.1", 200, 300),
             Event("collective-permute-start.1", 400, 400),
             Event("fusion.2", 400, 500),
             Event("collective-permute-done.1", 500, 600),
             Event("fusion.3", 600, 900)]
    devs = {"/device:TPU:0": chip0,
            "/device:TPU:1": [Event("all-reduce.1", 0, 100)]}
    devs = dict(list(devs.items())[:chips])
    return from_events(devs, [Event("bench.window", 0, 1000)], hlo)


@pytest.mark.parametrize("chips,total_ns,exposed_ns",
                         [(1, 400, 200), (2, 500, 300)])
def test_collective_readers(chips, total_ns, exposed_ns):
    run = _Run(_trace(chips), diagrams=2)
    total = registry.metric_reader(ROOT, "collective_s.shard4").read(run)
    exposed = registry.metric_reader(
        ROOT, "collective_exposed_s.shard4").read(run)
    assert total == pytest.approx(total_ns * 1e-9 / chips / 2)
    assert exposed == pytest.approx(exposed_ns * 1e-9 / chips / 2)


def test_collective_readers_silent_without_collectives():
    """No collective op: a hand-built trace, none, and the recorded
    one-chip TPU trace (whose async copies are not collectives)."""
    from bench.tracing import load_file
    hand = from_events({"/device:TPU:0": [Event("fusion.1", 0, 10)]},
                       [Event("bench.window", 0, 100)])
    chip = load_file(str(ROOT / "bench" / "tests" / "data"
                         / "tpu_v5e_64.xplane.pb"))
    for name in ("collective_s.shard4", "collective_exposed_s.shard4"):
        reader = registry.metric_reader(ROOT, name)
        for trace in (hand, None, chip):
            assert reader.read(_Run(trace, 1)) is None
