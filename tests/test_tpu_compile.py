"""Compile rehearsals: the store kernels compiled by the TPU's own compiler
for a described (not attached) v5e chip, at the largest shape buckets the
q8 autoscaling episode reaches.  Interpret-mode tests cannot see what this
refuses: blocks not aligned to the chip's tiling, more fast memory than a
kernel may use, 64-bit integer lanes.

Every compile rehearsal lives in this one file: only one process may load
the TPU compiler's library, so the topology is described in a fixture of
the test that needs it, never at import time.
"""
import os

import pytest

# q8-justin episode maxima: tables of 2,400,000 keys probed by up to 4,742
# queries; consolidations of up to 2,436,496 events into 2,400,000 segments
MAX_TABLE, MAX_QUERIES = 2_400_000, 4_742
MAX_EVENTS, MAX_SEGMENTS = 2_436_496, 2_400_000
V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:           # no TPU compiler library here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a rehearsal compile cannot be read back without a chip: keep it out
    # of the persistent cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, shapes, one_chip, **static):
    import jax
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = fn.lower(*args, **static).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the kernel carries its own name, which a profiler trace shows
    assert f"/{fn.__name__}/pallas_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < V5E_HBM_BYTES, used
    return compiled


def test_sorted_probe_compiles_for_v5e_at_episode_max(one_chip):
    import jax.numpy as jnp

    from repro.kernels.device import bucket
    from repro.kernels.sorted_probe.kernel import (QUERY_BLOCK, TABLE_TILE,
                                                   sorted_probe)
    t = bucket(MAX_TABLE, TABLE_TILE)
    n = bucket(MAX_QUERIES, QUERY_BLOCK)
    assert t >= MAX_TABLE and n >= MAX_QUERIES
    _compile(sorted_probe, [((t,), jnp.int32)] * 2 + [((n,), jnp.int32)] * 2,
             one_chip, interpret=False)


def test_window_agg_compiles_for_v5e_at_episode_max(one_chip):
    import jax.numpy as jnp

    from repro.kernels.device import bucket
    from repro.kernels.window_agg.kernel import (EVENT_TILE, SEG_BLOCK,
                                                 window_agg)
    e = bucket(MAX_EVENTS, EVENT_TILE)
    s = bucket(MAX_SEGMENTS, SEG_BLOCK)
    assert e >= MAX_EVENTS and s >= MAX_SEGMENTS
    _compile(window_agg, [((e,), jnp.int32), ((1, e), jnp.float32)],
             one_chip, n_segments=s, interpret=False)
