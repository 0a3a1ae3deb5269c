"""The four-slab shardmap deployment through the pipeline: the shardmap
rows program's fields against the host scatter, its diagrams against
``compute_dms`` and the benchmark's plain reference, one compile per
shape, and the error for a z extent four slabs do not divide.

One subprocess forces four host devices (the count must never leak into
other tests, which see one device) and reports every check as JSON; each
check is a case of its own."""

import json
import os
import subprocess
import sys

import pytest

FIELDS = [f"{g}-{fam}" for g in ("16x12x8", "10x7x12", "8x8x4")
          for fam in ("random", "wavelet")]
DIAGRAMS = [f"{name}-{ref}" for name in ("random", "wavelet", "ridge")
            for ref in ("compute_dms", "reference")]


@pytest.fixture(scope="module")
def shard4():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(__file__),
                                      "shard4_check.py")],
        capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("case", FIELDS)
def test_slab_fields_match_host_scatter(shard4, case):
    """Every pair and flag array, its dtype and the critical count of a
    field built slab by slab on four devices equal the host scatter of
    the one-chip kernel's rows (y / x not multiples of 8 / 128, and one
    plane a slab)."""
    assert shard4["fields"][case] == []


def test_slab_words_match_one_chip_words(shard4):
    """Where the fields would need int64 sids the program returns the
    slabs' kernel words for the host scatter: the one-chip words."""
    assert shard4["words"] is True


@pytest.mark.parametrize("case", DIAGRAMS)
def test_diagram_matches_references(shard4, case):
    """No point differs from the sequential pipeline or from the plain
    reference, the ridge field's v-paths crossing every slab boundary
    included."""
    assert shard4["mismatch"][case] == 0


def test_one_compile_over_three_diagrams(shard4):
    assert shard4["compiles"] == {"builds": 1, "hits": 2, "jit_programs": 1}


def test_backend_gradient_reuses_the_program(shard4):
    """``Backend.gradient`` of ``shardmap`` (the stage-chain entry) runs
    the pipeline's cached program and gives the host scatter's field."""
    assert shard4["gradient"] == {"same": True, "builds": 1}


def test_stage_counters(shard4):
    """Fields from the devices, four blocks, and per field two rank
    planes plus the ghost plane's 2 x 19 words of 16 x 16 vertices."""
    for stats in shard4["stats"].values():
        assert stats["device_fields"] == 1 and stats["n_blocks"] == 4
        assert stats["halo_bytes"] == (2 + 2 * 19) * 16 * 16 * 4


def test_z_extent_must_divide(shard4):
    assert "nz=6 does not divide evenly over n_blocks=4" \
        in shard4["divisibility_error"]
