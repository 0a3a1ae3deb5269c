"""The trace reduction on hand-built event lists and on recorded traces."""

from __future__ import annotations

from pathlib import Path

import pytest

from bench import tracing
from bench.tracing import Event

DATA = Path(__file__).resolve().parent / "data"


def test_union_merges_overlaps_and_clips():
    iv = [(0, 10), (5, 15), (20, 30), (29, 31), (40, 50)]
    assert tracing.union_ns(iv, 0, 100) == 15 + 11 + 10
    assert tracing.union_ns(iv, 8, 25) == 7 + 5
    assert tracing.union_ns([], 0, 10) == 0


def test_gaps_are_the_complement():
    iv = [(5, 10), (8, 12), (20, 25)]
    assert tracing.gaps_ns(iv, 0, 30) == [(0, 5), (12, 20), (25, 30)]
    assert tracing.gaps_ns(iv, 6, 22) == [(12, 20)]
    assert tracing.gaps_ns([(0, 30)], 0, 30) == []


def test_attribute_takes_the_innermost_span():
    spans = [Event("bench.diagram", 0, 100), Event("stage.d1", 40, 60)]
    assert tracing.attribute((45, 55), spans) == "stage.d1"
    assert tracing.attribute((10, 20), spans) == "bench.diagram"
    assert tracing.attribute((200, 210), spans) == "untraced"


def _trace():
    dev = {"/device:TPU:0": [Event("fusion.1", 100, 200),
                             Event("_fused_call.1", 300, 600),
                             Event("fusion.1", 550, 650)],
           "/device:TPU:1": [Event("_fused_call.1", 300, 500)]}
    host = [Event("bench.window", 0, 1000), Event("stage.gradient", 250, 700),
            Event("stage.d1", 700, 1000)]
    return tracing.from_events(dev, host)


def test_trace_numbers():
    tr = _trace()
    assert tr.window == (0, 1000)
    assert tr.window_s == pytest.approx(1e-6)
    # device 0 busy 100 + 350, device 1 busy 200 -> mean 325 ns
    assert tr.busy_s() == pytest.approx(325e-9)
    assert tr.idle_share() == pytest.approx(1 - 0.325)
    assert tr.kernel_s("_fused_call") == pytest.approx(250e-9)
    top = dict(tr.top_ops())
    assert top["_fused_call.1"] == pytest.approx(250e-9)
    gaps = tr.idle_gaps()
    assert gaps[0] == ["stage.d1", pytest.approx(350e-9)]
    assert [g[0] for g in gaps] == ["stage.d1", "bench.window",
                                    "stage.gradient"]


KERNEL_HLO = (
    "%_fused_call.1 = (s32[1,4,19,8,128]{4,3,2,1,0:T(8,128)}, "
    "s32[1,4,19,8,128]{4,3,2,1,0:T(8,128)}) custom-call("
    "s32[1,6,8,128]{3,2,1,0:T(8,128)S(1)} %pad, "
    "s32[1,6,8,128]{3,2,1,0:T(8,128)S(1)} %pad, "
    "s32[3,74,8,128]{3,2,1,0:T(8,128)S(1)} %broadcast_in_dim.2), "
    'custom_call_target="tpu_custom_call"')


def test_hlo_bytes_count_results_and_distinct_operands():
    written = 2 * 4 * 19 * 8 * 128 * 4
    read = 6 * 8 * 128 * 4 + 3 * 74 * 8 * 128 * 4   # %pad counted once
    assert tracing.hlo_bytes(KERNEL_HLO) == written + read
    assert tracing.hlo_bytes(
        "%fusion.1 = u32[512]{0:T(512)S(1)} fusion(u32[64]{0:T(128)} "
        "%get-tuple-element.226, s32[1024]{0:T(1024)S(1)} %pad_clamp), "
        "kind=kCustom") == 512 * 4 + 64 * 4 + 1024 * 4


class _Run:
    peaks = {"hbm_bytes_per_s": 819e9}

    def __init__(self, trace):
        self.trace = trace


def test_roofline_reader():
    from bench import registry
    from bench.tests.conftest import ROOT
    reader = registry.metric_reader(ROOT, "lower_star_roofline")
    host = [Event("bench.window", 0, 10 ** 9)]
    dev = {"/device:TPU:0": [Event("_fused_call.1", 0, 10 ** 6),
                             Event("fusion.2", 10 ** 6, 2 * 10 ** 6)]}
    tr = tracing.from_events(dev, host, {"_fused_call.1": KERNEL_HLO})
    least = tracing.hlo_bytes(KERNEL_HLO) / 819e9
    assert reader.read(_Run(tr)) == pytest.approx(100 * least / 1e-3)
    # device ops but not the kernel's: an error, not a silent gap
    other = tracing.from_events({"/device:TPU:0": dev["/device:TPU:0"][1:]},
                                host)
    with pytest.raises(LookupError):
        reader.read(_Run(other))
    # no device plane (a CPU): nothing to read
    assert reader.read(_Run(tracing.from_events({}, host))) is None
    assert reader.read(_Run(None)) is None


def test_trace_needs_a_window():
    with pytest.raises(ValueError):
        tracing.from_events({}, [Event("bench.diagram", 0, 1)])


def test_recorded_cpu_trace(tmp_path):
    """A trace recorded here: host spans come through, and a CPU has no
    device plane, so there is nothing to call busy."""
    import jax
    import jax.numpy as jnp
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.diagram"):
            (jnp.ones((64, 64)) @ jnp.ones((64, 64))).block_until_ready()
    jax.profiler.stop_trace()
    tr = tracing.load(str(tmp_path))
    assert tr.window_s > 0
    assert sorted(s.name for s in tr.host_spans) == ["bench.diagram",
                                                     "bench.window"]
    assert tr.idle_share() is None and tr.busy_s() == 0.0


def test_recorded_tpu_trace():
    """A trace recorded on a TPU v5e (``bench/tools/trace_look.py``, one
    64^3 wavelet-seeded diagram): ops come from the device plane's
    ``XLA Ops`` line under their instruction names, the fused kernel is
    found by name, and idle gaps are named by the program's stages."""
    tr = tracing.load_file(str(DATA / "tpu_v5e_64.xplane.pb"))
    assert list(tr.device_ops) == ["/device:TPU:0"]
    assert 0 < tr.busy_s() < tr.window_s
    kernel = tr.kernel_s("_fused_call")
    assert 0.02 < kernel < 0.03
    # 64^3 padded to 128 lanes: the halo volume and face table read once,
    # the two (1, 64, 19, 64, 128) word arrays written once
    assert tracing.hlo_bytes(tr.hlo["_fused_call.1"]) == (
        66 * 64 * 128 * 4 + 3 * 74 * 8 * 128 * 4 + 2 * 64 * 19 * 64 * 128 * 4)
    assert tr.top_ops(1)[0][0] == "_fused_call.1"
    assert tr.top_ops(1)[0][1] == pytest.approx(kernel)
    gaps = tr.idle_gaps(3)
    assert gaps[0][0] == "stage.extract_sort"
    assert all(name.startswith("stage.") for name, _ in gaps)
    assert {s.name for s in tr.host_spans} >= {"bench.window",
                                               "bench.diagram",
                                               "stage.extract_sort"}
