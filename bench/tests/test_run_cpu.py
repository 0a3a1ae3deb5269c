"""Every cell rehearsed end to end on the CPU at a tiny size, and the real
command's refusals."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench.tests.conftest import ROOT, run_cell

CELLS = [w["name"] for w in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]


def _names(kind, cell):
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    return {m["name"] for m in bm[kind]
            if "workloads" not in m or cell in m["workloads"]}


@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearsal(tiny_root, capsys, cell):
    res = run_cell(tiny_root, cell, capsys)
    assert res["correct"] is True, res["compared"]
    assert set(res["metrics"]) == _names("end_to_end", cell)
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert list(res)[-1] == "compared"
    assert res["compared"]["mismatched_points"]["value"] == 0


@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearsal_traced(tiny_root, capsys, cell):
    res = run_cell(tiny_root, cell, capsys, trace=1)
    assert res["correct"] is True
    # on a CPU the trace has no device plane: the device readers are
    # silent, the others report
    assert set(res["metrics"]) <= _names("per_layer", cell)
    assert {n for n in res["metrics"] if n.startswith("window_compiles")}
    assert res["device"]["window_s"] > 0


def _bench_cmd(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "volume-random-64",
         "--seed", "0", "--seconds", "10", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_real_command_refuses_a_cpu():
    p = _bench_cmd(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    """A directory with only BENCHMARK.json and bench/ has no program."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _bench_cmd(tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_stage_spans_cover_every_stage(tmp_path):
    """In a traced window every stage of a diagram, the batched gradient
    among them, shows as a ``stage.*`` host span."""
    import jax
    from bench import fields, run, tracing
    from repro.core.grid import Grid
    from repro.pipeline import PersistencePipeline, TopoRequest
    dims = (6, 6, 6)
    pipe = PersistencePipeline(backend="jax")
    req = TopoRequest(field=fields.make("random", dims, 1, 0),
                      grid=Grid.of(*dims))
    pipe.run(req)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    with run.stage_spans():
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench.window"):
            res = pipe.run(req)
        jax.profiler.stop_trace()
    names = {s.name for s in tracing.load(str(tmp_path)).host_spans}
    assert {"stage." + c.name for c in res.report.children} <= names
    assert "stage.gradient" in names
