"""Distributed streaming data plane on a device mesh (subprocess-free demo).

The control plane (Justin/DS2, placement) is host-side Python — like Flink's
JobManager; this shows the DATA plane running on devices: keyed events are
hash-partitioned over the mesh with shard_map and each shard aggregates its
keys with the window_agg kernel (masked segment sums on the vector unit,
see src/repro/kernels/window_agg/), here in the Pallas interpreter.

Run:  PYTHONPATH=src python examples/streaming_on_mesh.py
(uses 8 virtual CPU devices)
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.kernels.window_agg.kernel import window_agg

N_TASKS = 8                      # operator parallelism = mesh size
N_KEYS = 256                     # keyspace (per-task segment range)

mesh = jax.make_mesh((N_TASKS,), ("tasks",))
rng = np.random.default_rng(0)

# one tick of events, already hash-partitioned to tasks (the engine's job)
events_per_task = 4096
keys = rng.integers(0, N_KEYS, (N_TASKS, events_per_task)).astype(np.int32)
vals = rng.normal(size=(N_TASKS, events_per_task, 4)).astype(np.float32)


def task_fn(k, v):
    """One task's window aggregation (runs per mesh shard): the value
    columns and a row of ones, whose sums are the per-key counts."""
    rows = jnp.concatenate([v[0].T, jnp.ones((1, v.shape[1]), v.dtype)])
    out = window_agg(k[0], rows, N_KEYS, interpret=True)     # [V + 1, keys]
    return out[:-1].T[None], out[-1][None]


agg = jax.jit(jax.shard_map(task_fn, mesh=mesh,
                            in_specs=(P("tasks", None), P("tasks", None, None)),
                            out_specs=(P("tasks", None, None), P("tasks", None)),
                            check_vma=False))   # pallas_call returns no vma
sums, counts = agg(jnp.asarray(keys), jnp.asarray(vals))
print(f"mesh: {mesh.shape}; per-task sums {sums.shape}, counts {counts.shape}")

# verify against a host-side oracle
ref_counts = np.zeros((N_TASKS, N_KEYS))
for t in range(N_TASKS):
    ref_counts[t] = np.bincount(keys[t], minlength=N_KEYS)
assert np.allclose(np.asarray(counts), ref_counts), "count mismatch"
total = np.asarray(counts).sum()
print(f"aggregated {int(total)} events across {N_TASKS} mesh shards — OK")
