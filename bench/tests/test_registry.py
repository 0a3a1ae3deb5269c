"""The harness finds configurations, traffic mixes, field families,
drivers and metric readers by name: a throwaway set is picked up with no
edit to any file the benchmark already has."""

from __future__ import annotations

import json

from bench import registry
from bench.tests.conftest import run_cell


def test_real_cells_resolve():
    from bench.tests.conftest import ROOT
    bm = registry.benchmark(ROOT)
    for wl in bm["workloads"]:
        cell = registry.cell(ROOT, wl["name"])
        registry.driver(ROOT, cell.config["driver"])
        assert cell.end_to_end and cell.per_layer
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        for m in cell.per_layer:
            assert hasattr(registry.metric_reader(ROOT, m["name"]), "read")


def test_throwaway_config_mix_and_metric_are_found(tiny_root, capsys):
    bm = json.loads((tiny_root / "BENCHMARK.json").read_text())
    (tiny_root / "bench" / "configs" / "throwaway.json").write_text(
        json.dumps({"name": "throwaway", "driver": "volume",
                     "pipeline": {"backend": "np"}}))
    (tiny_root / "bench" / "traffic" / "throwaway-mix.json").write_text(
        json.dumps({"family": "throwaway-ramp", "dims": [5, 4, 3],
                    "pool": 4, "check_samples": 1}))
    (tiny_root / "bench" / "families" / "throwaway-ramp.py").write_text(
        "import numpy as np\n"
        "def make(dims, seed, i):\n"
        "    n = dims[0] * dims[1] * dims[2]\n"
        "    r = np.random.default_rng([seed, i])\n"
        "    return r.permutation(n).astype(np.float32)\n")
    (tiny_root / "bench" / "metrics" / "throwaway.count.py").write_text(
        "def read(run):\n    return float(run.window['attempted'])\n")
    bm["configs"].append({"name": "throwaway", "source": "none",
                          "file": "bench/configs/throwaway.json",
                          "reduced": [], "why": "test"})
    bm["workloads"].append({"name": "throwaway-cell", "config": "throwaway",
                            "traffic": "throwaway-mix", "chips": 1,
                            "why": "test"})
    bm["per_layer"].append({"name": "throwaway.count", "unit": "diagrams",
                            "better": "higher", "source": "program_counter",
                            "layer": "test", "moves": "exact_vertices_per_s",
                            "workloads": ["throwaway-cell"]})
    bm["end_to_end"][0]["workloads"].append("throwaway-cell")
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(bm))
    cell = registry.cell(tiny_root, "throwaway-cell")
    assert [m["name"] for m in cell.per_layer] == ["throwaway.count"]
    fam = registry.family(tiny_root, "throwaway-ramp")
    assert sorted(fam.make((5, 4, 3), 7, 0)) == list(range(60))
    res = run_cell(tiny_root, "throwaway-cell", capsys, trace=1)
    assert res["correct"] is True
    assert res["metrics"]["throwaway.count"]["value"] == res["attempted"]
    res = run_cell(tiny_root, "throwaway-cell", capsys, trace=0)
    assert set(res["metrics"]) == {"exact_vertices_per_s", "setup_s"}


def test_unknown_family_is_refused(tiny_root):
    import pytest
    from bench import fields
    with pytest.raises(FileNotFoundError):
        fields.make("no-such-family", (3, 3, 3), 0, 0, root=tiny_root)
