"""Compiles of the main path's device programs for a described TPU v5e.

The TPU compiler is installed with jax; it compiles for a chip that is
described and not attached (``topologies.get_topology_desc``), so these
tests catch what interpret mode cannot — illegal block shapes, 64-bit
values inside a Mosaic kernel, more VMEM or HBM than the chip has —
without a chip.  Nothing runs.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and every test worker
imports this file.
"""

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

HBM_BYTES = 16 * 2 ** 30        # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes, sharding, hbm_bytes=HBM_BYTES):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = fn.lower(*args).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < hbm_bytes, f"{used / 2 ** 30:.2f} GiB > {hbm_bytes:,} B"
    return compiled.as_text()


@pytest.mark.parametrize("shape", [(1, 258, 256, 256), (8, 66, 64, 64)],
                         ids=["256^3", "64^3x8"])
def test_fused_kernel_compiles(one_chip, shape):
    """The fused front-end kernel lowers through Mosaic with int32 ranks:
    one 256^3 volume, and a 64^3 serving batch of 8."""
    from repro.kernels.lower_star import _fused_call
    fn = jax.jit(lambda v: _fused_call(v, interpret=False))
    assert "tpu_custom_call" in _compile(fn, (shape, jnp.int32),
                                         sharding=one_chip)


def test_fused_fields_program_compiles(one_chip):
    """The fused rows program of one 128^3 volume, kernel and device
    fields: no XLA scatter, and under 1.5 GB of device memory (no
    lane-padded (..., T_k) intermediate)."""
    from repro.core.grid import Grid
    from repro.kernels.lower_star import _fused_call, fields_from_words
    g = Grid.of(128, 128, 128)
    fn = jax.jit(lambda v: fields_from_words(_fused_call(v, interpret=False),
                                             g))
    text = _compile(fn, ((1, 130, 128, 128), jnp.int32), sharding=one_chip,
                    hbm_bytes=1.5e9)
    assert "tpu_custom_call" in text
    assert " scatter(" not in text


def test_streamed_chunk_compiles(one_chip):
    """The XLA chunk kernel on rank-free int64 keys: one 8-plane 256^2
    chunk plus its two ghost planes."""
    from repro.kernels.ops import _halo_rows_jax
    assert "tpu_custom_call" not in _compile(
        _halo_rows_jax, ((10, 256, 256), jnp.int64), sharding=one_chip)


def test_d0_round_compiles(one_chip):
    """One jitted round of the D0 pointer-jumping pairing."""
    from repro.kernels.sandwich import _bucket, _d0_round
    n_pad, m_pad = _bucket(50_000), _bucket(25_000)
    shapes = [((n_pad,), jnp.int64)] * 3 + [((m_pad,), jnp.int64)] * 3
    _compile(_d0_round(n_pad, m_pad), *shapes, sharding=one_chip)


def test_shardmap_slab_program_compiles(topo, monkeypatch):
    """The shardmap backend's program for one 128^3 volume in four
    z-slabs on the four chips of a v5e 2x2: the ring exchanges as
    collective-permutes, the fused kernel and the fields on each chip,
    no XLA scatter, no (nv_local, 27) neighbour tensor, and a small
    share of each chip's memory."""
    import re
    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P
    from repro.core.grid import Grid
    from repro.distributed.shardmap_pipeline import slab_program
    from repro.kernels import lower_star
    # the program asks the platform (the CPU here) whether to interpret
    monkeypatch.setattr(lower_star, "interpret_mode", lambda: False)
    assert len(topo.devices) == 4
    mesh = Mesh(np.asarray(topo.devices), ("blocks",))
    g = Grid.of(128, 128, 128)
    arg = jax.ShapeDtypeStruct((1, g.nv), jnp.int32,
                               sharding=NamedSharding(mesh, P(None, "blocks")))
    compiled = slab_program(g, mesh).lower(arg).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "collective-permute" in text
    assert " scatter(" not in text
    assert not re.search(r"\[[\d,]*,27\]", text)
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < 0.5e9, f"{used:,} B a chip"
