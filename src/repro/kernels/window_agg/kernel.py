"""Pallas TPU kernel: keyed window aggregation (segment sum).

TPU adaptation: scatter-add, the GPU/CPU idiom for keyed aggregation, has
no efficient TPU analogue (no per-lane atomics).  Instead each (segment
block x event tile) cell compares every event's segment id with every
segment of the block and adds the matching values: a dense masked sum on
the VPU.  Additions of integer-valued float32 are exact below 2^24, so
integer weights sum exactly whatever the matmul precision.  (A one-hot
matmul would spend the MXU on a product with one useful column.)

Layout: segment ids ``(rows, 128)`` and values ``(V, rows, 128)``,
lane-major; one event row broadcast over the sublanes meets a block of
segment ids that runs down the sublanes.  Per-lane partial sums
accumulate in VMEM scratch across the sequential event-tile axis and are
reduced over lanes once per segment block.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
EVENT_TILE = 8192
SEG_BLOCK = 512


def _agg_kernel(seg_ref, val_ref, sum_ref, acc_ref):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)                           # first event tile
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    sid = jax.lax.broadcasted_iota(jnp.int32, (SEG_BLOCK, LANES), 0) \
        + i * SEG_BLOCK                        # segment id, down the sublanes
    accs = [acc_ref[v] for v in range(acc_ref.shape[0])]
    for r in range(EVENT_TILE // LANES):       # one 128-event row each
        hit = seg_ref[r:r + 1, :] == sid       # [SEG_BLOCK, 128]
        for v, acc in enumerate(accs):
            accs[v] = acc + jnp.where(hit, val_ref[v, r:r + 1, :], 0.0)
    for v, acc in enumerate(accs):
        acc_ref[v] = acc

    @pl.when(j == pl.num_programs(1) - 1)      # last event tile
    def _emit():
        sum_ref[...] = jnp.sum(acc_ref[...], axis=2)


@partial(jax.jit, static_argnames=("n_segments", "interpret"))
def window_agg(seg_ids: jax.Array, values: jax.Array, n_segments: int, *,
               interpret: bool):
    """seg_ids: [N] int32 (ids outside [0, n_segments) match nothing);
    values: [V, N] float32.  Returns sums [V, n_segments]."""
    v, n = values.shape
    n_pad = (-n) % EVENT_TILE
    s_pad = (-n_segments) % SEG_BLOCK
    seg_ids = jnp.pad(seg_ids, (0, n_pad), constant_values=-1)
    values = jnp.pad(values, ((0, 0), (0, n_pad)))
    rows = EVENT_TILE // LANES
    sums = pl.pallas_call(
        _agg_kernel,
        grid=((n_segments + s_pad) // SEG_BLOCK, (n + n_pad) // EVENT_TILE),
        in_specs=[
            pl.BlockSpec((rows, LANES), lambda i, j: (j, 0)),
            pl.BlockSpec((v, rows, LANES), lambda i, j: (0, j, 0)),
        ],
        out_specs=pl.BlockSpec((v, SEG_BLOCK), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((v, n_segments + s_pad), jnp.float32),
        scratch_shapes=[pltpu.VMEM((v, SEG_BLOCK, LANES), jnp.float32)],
        interpret=interpret,
        name="window_agg",
    )(seg_ids.reshape(-1, LANES), values.reshape(v, -1, LANES))
    return sums[:, :n_segments]
