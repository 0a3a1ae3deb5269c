"""A run with the timed path broken underneath must read as not correct:
one case for each fault these cells can have.  The volume cells run one
field per call on one chip, so there is no batch to halve and no
exchange between chips to leave out."""

from __future__ import annotations

import copy
import json

import pytest

from bench.tests.conftest import ROOT, run_cell

CELLS = [w["name"] for w in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]


def _drop_pairs(res, dim):
    res = copy.copy(res)
    diagram = copy.copy(res.diagram)
    diagram.pairs = dict(diagram.pairs)
    diagram.pairs[dim] = diagram.pairs[dim][:0]
    res.diagram = diagram
    return res


@pytest.mark.parametrize("cell", CELLS)
def test_altered_answer_is_caught(tiny_root, capsys, monkeypatch, cell):
    """An answer altered where it is produced: D0 pairs lost."""
    from repro.pipeline import api
    orig = api.PersistencePipeline.run

    def run(self, *a, **kw):
        return _drop_pairs(orig(self, *a, **kw), 0)

    monkeypatch.setattr(api.PersistencePipeline, "run", run)
    res = run_cell(tiny_root, cell, capsys)
    assert res["correct"] is False
    assert res["compared"]["mismatched_points"]["value"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_unchanged_state_is_caught(tiny_root, capsys, monkeypatch, cell):
    """A step that returns its state unchanged: every call after the
    warm-up answers with the warm-up field's diagram."""
    from repro.pipeline import api
    orig = api.PersistencePipeline.run
    first = []

    def run(self, *a, **kw):
        if not first:
            first.append(orig(self, *a, **kw))
        return first[0]

    monkeypatch.setattr(api.PersistencePipeline, "run", run)
    res = run_cell(tiny_root, cell, capsys)
    assert res["correct"] is False
