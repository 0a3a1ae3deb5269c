"""Shared fixtures of the benchmark's tests (run on the CPU)."""

from __future__ import annotations

import json
import os
import shutil
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

# every cell's traffic cut to a size the CPU runs in seconds
TINY_DIMS = {"wavelet-seeded-128": [8, 8, 8], "random-64": [6, 6, 6]}


def make_root(dest: Path) -> Path:
    """A checkout whose cells run at tiny sizes: the real BENCHMARK.json,
    configurations, drivers, field families and metric readers; traffic
    cut down."""
    shutil.copy(ROOT / "BENCHMARK.json", dest / "BENCHMARK.json")
    (dest / "bench").mkdir()
    for sub in ("configs", "drivers", "families", "metrics", "traffic"):
        shutil.copytree(ROOT / "bench" / sub, dest / "bench" / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    for p in (dest / "bench" / "traffic").glob("*.json"):
        t = json.loads(p.read_text())
        t["dims"] = TINY_DIMS.get(p.stem, [6, 6, 6])
        p.write_text(json.dumps(t))
    (dest / "src").symlink_to(ROOT / "src")
    return dest


@pytest.fixture
def tiny_root(tmp_path) -> Path:
    return make_root(tmp_path)


def run_cell(root: Path, workload: str, capsys, seed: int = 2 ** 31 + 5,
             seconds: float = 1.0, trace: int = 0) -> dict:
    """One rehearsal run of a cell on the CPU; returns its result line."""
    from bench import run
    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", str(trace)],
                  root=root, require_tpu=False)
    out = capsys.readouterr()
    assert rc == 0, out.err[-2000:]
    return json.loads(out.out.strip().splitlines()[-1])
