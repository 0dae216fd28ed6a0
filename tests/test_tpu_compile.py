"""The main path's device programs compile for a described TPU v5e.

No chip runs here: the TPU compiler that ships with jaxlib compiles for a
``v5e:2x2`` topology that is described, not attached (on-chip-measurement
guide §2), and refuses what the chip's compiler would refuse — the way the
old Pallas fused-jump kernel was refused ("Cannot do int indexing on
TPU").  One chip: the hybrid build's prep, reduce-chunk, handoff-sort,
pack, slice and pst programs.  Four chips: the mesh build's chunk and
tail-gather programs over a 4-device mesh.  None of these is a custom
kernel, so none may carry a ``tpu_custom_call``.

The topology is described in a module-scoped fixture, never at import: only
one process may hold the TPU library, and under several pytest workers a
description made at import would fail on all but one of them.  Sizes are
small (compiles cost the same 10-30 s at any size here); the full
orkut-scale rehearsal is a builder's scratch script, recorded in PERF.md.
"""

import os

import numpy as np
import pytest

LOG_N = 14
N = 1 << LOG_N
E = 8 << LOG_N  # edge records; pow2, so it is also the padded width


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a described chip's executables can be written to the persistent
    # cache but never read back without the chip: keep them out of it
    prior = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", prior)
        cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    from jax.sharding import Mesh
    from sheep_tpu.parallel.mesh import AXIS
    return Mesh(np.array(topo.devices[:4]), (AXIS,))


@pytest.fixture
def accel_defaults(monkeypatch):
    """Trace the accelerator branch of the one trace-time platform
    choice on this path: this process's default backend is the CPU."""
    monkeypatch.setenv("SHEEP_SORT_PACK64", "0")


def _compile(fn, *args, **static):
    import jax
    # a fresh jit of the undecorated function: never reuse a trace made
    # for the CPU backend by another test in this worker
    statics = tuple(static)
    c = jax.jit(fn.__wrapped__, static_argnames=statics).lower(
        *args, **static).compile()
    assert "tpu_custom_call" not in c.as_text()
    return c


def _s(shape, dtype, sharding):
    import jax
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_prepare_links_compiles(one_chip):
    import jax.numpy as jnp
    from sheep_tpu.ops.build import prepare_links
    c = _compile(prepare_links, _s((E,), jnp.uint32, one_chip),
                 _s((E,), jnp.uint32, one_chip), n=N, with_pst=False)
    assert c.memory_analysis().argument_size_in_bytes >= 8 * E


@pytest.mark.parametrize("levels", [4, 12])
def test_fixpoint_chunk_compiles(one_chip, accel_defaults, levels):
    import jax.numpy as jnp
    from sheep_tpu.ops.forest import fixpoint_chunk
    width = E >> (0 if levels == 4 else 2)  # full width, then compacted
    _compile(fixpoint_chunk, _s((width,), jnp.int32, one_chip),
             _s((width,), jnp.int32, one_chip), n=N, levels=levels,
             jrounds=1)


def test_handoff_sort_pack_and_slice_compile(one_chip, accel_defaults):
    import jax.numpy as jnp
    from sheep_tpu.ops.build import _slice_rows, _sort_by_hi_prog
    from sheep_tpu.ops.forest import pack_links_6b
    w = E // 8
    _compile(_sort_by_hi_prog, _s((w,), jnp.int32, one_chip),
             _s((w,), jnp.int32, one_chip))
    _compile(pack_links_6b, _s((w,), jnp.int32, one_chip),
             _s((w,), jnp.int32, one_chip))
    _compile(_slice_rows, _s((w, 6), jnp.uint8, one_chip),
             _s((), jnp.int32, one_chip), length=w // 4)


def test_pst_weights_compiles(one_chip):
    import jax.numpy as jnp
    from sheep_tpu.ops.forest import pst_weights
    _compile(pst_weights, _s((E,), jnp.int32, one_chip), n=N)


def test_chunk_sharded_compiles_on_four_chips(mesh4, accel_defaults):
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from sheep_tpu.parallel.chunked import chunk_sharded
    from sheep_tpu.parallel.mesh import AXIS
    sh = NamedSharding(mesh4, P(AXIS, None))
    c = _compile(chunk_sharded, _s((4, E // 4), jnp.int32, sh),
                 _s((4, E // 4), jnp.int32, sh), n=N, mesh=mesh4,
                 levels=4, jrounds=1, global_f=True)
    # the global one-step table is combined across the mesh
    assert "all-reduce" in c.as_text()


def test_gather_links_replicated_compiles_on_four_chips(mesh4):
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from sheep_tpu.parallel.chunked import gather_links_replicated
    from sheep_tpu.parallel.mesh import AXIS
    sh = NamedSharding(mesh4, P(AXIS, None))
    c = _compile(gather_links_replicated, _s((4, E // 32), jnp.int32, sh),
                 _s((4, E // 32), jnp.int32, sh), mesh=mesh4)
    assert "all-gather" in c.as_text()
