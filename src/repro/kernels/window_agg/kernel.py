"""Pallas TPU kernel: keyed window aggregation (segment sum) over sorted ids.

TPU adaptation: scatter-add, the GPU/CPU idiom for keyed aggregation, has
no efficient TPU analogue (no per-lane atomics).  Instead each event row
is compared with every segment of a block and the matching values are
added: a dense masked sum on the VPU.  Additions of integer-valued float32
are exact below 2^24, so integer weights sum exactly whatever the matmul
precision.  (A one-hot matmul would spend the MXU on a product with one
useful column.)

Banded grid: the ids arrive sorted, so the events of one segment block
are one contiguous range, and an event tile meets only the blocks its
first and last ids span.  The grid walks a merge path through (event
tile, segment block) pairs: each step moves to the next tile or to the
next block, whichever range ends first, so it visits every pair that
shares an event, each segment block in one run of consecutive steps (its
output block stays in VMEM across the run), and ``tiles + blocks - 1``
steps in all.  A scalar-prefetched schedule, computed in the jitted entry
from the ids, gives each step its tile and the rows of that tile whose
events fall in the step's block; a step compares only those rows, so
every 128-event row is compared with the one or few blocks its ids
touch.  Padded events (id -1, after the sorted ids) fall in no block and
are never compared.

Layout: segment ids ``(rows, 128)`` and values ``(V, rows, 128)``,
lane-major; one event row broadcast over the sublanes meets a block of
segment ids that runs down the sublanes.  Per-lane partial sums
accumulate in VMEM scratch over a step's rows and are reduced over lanes
into the step's output block.
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
ROWS = EVENT_TILE // LANES
_ROW_BITS = 7                 # a row index 0..ROWS in the packed schedule
_ROW_MASK = (1 << _ROW_BITS) - 1


def _unpack(entry):
    """(tile, first row, end row) of one packed schedule entry."""
    return (entry >> 2 * _ROW_BITS, (entry >> _ROW_BITS) & _ROW_MASK,
            entry & _ROW_MASK)


def _schedule(seg_ids: jax.Array, n_blocks: int) -> jax.Array:
    """The merge path through (event tile, segment block) pairs, one packed
    int32 a step: ``tile << 14 | first row << 7 | end row``.  The step's
    block is its index less its tile.  ``seg_ids`` hold the sorted ids,
    then -1 padding."""
    n = seg_ids.shape[0]
    n_tiles = n // EVENT_TILE
    keys = jnp.where(seg_ids < 0, jnp.iinfo(jnp.int32).max, seg_ids)
    edges = jnp.arange(n_blocks + 1, dtype=jnp.int32) * SEG_BLOCK
    pos = jnp.searchsorted(keys, edges).astype(jnp.int32)  # block b: pos[b:b+2]
    # the path leaves tile t at the first block that ends at or past the
    # tile's end, so tile t + 1 starts at step t + 1 + (blocks that end
    # before tile t does); the last block runs to the end of the padding
    block_end = pos[1:].at[-1].set(n)
    later = jnp.arange(1, n_tiles, dtype=jnp.int32)
    starts = later + jnp.searchsorted(block_end, later * EVENT_TILE)
    n_steps = n_tiles + n_blocks - 1
    tile = jnp.zeros(n_steps, jnp.int32).at[starts].add(1).cumsum()
    block = jnp.arange(n_steps, dtype=jnp.int32) - tile
    lo = jnp.clip(pos[block] - tile * EVENT_TILE, 0, EVENT_TILE)
    hi = jnp.clip(pos[block + 1] - tile * EVENT_TILE, 0, EVENT_TILE)
    r0 = lo // LANES
    r1 = jnp.where(hi > lo, -(-hi // LANES), r0)
    return tile << 2 * _ROW_BITS | r0 << _ROW_BITS | r1


def _agg_kernel(sched_ref, seg_ref, val_ref, sum_ref, acc_ref):
    s = pl.program_id(0)
    tile, r0, r1 = _unpack(sched_ref[s])
    prev_tile, _, _ = _unpack(sched_ref[jnp.maximum(s - 1, 0)])

    @pl.when((s == 0) | (tile == prev_tile))   # first step on this block
    def _init():
        sum_ref[...] = jnp.zeros_like(sum_ref)

    @pl.when(r1 > r0)
    def _sum():
        base = (s - tile) * SEG_BLOCK
        sid = jax.lax.broadcasted_iota(jnp.int32, (SEG_BLOCK, LANES), 0)
        acc_ref[...] = jnp.zeros_like(acc_ref)

        def row(r, carry):                     # one 128-event row
            hit = seg_ref[pl.ds(r, 1), :] - base == sid   # [SEG_BLOCK, 128]
            for v in range(acc_ref.shape[0]):
                acc_ref[v] += jnp.where(hit, val_ref[v, pl.ds(r, 1), :], 0.0)
            return carry

        jax.lax.fori_loop(r0, r1, row, 0)
        sum_ref[...] += jnp.sum(acc_ref[...], axis=2)


@partial(jax.jit, static_argnames=("n_segments", "interpret"))
def window_agg(seg_ids: jax.Array, values: jax.Array, n_segments: int, *,
               interpret: bool):
    """seg_ids: [N] int32, non-negative and ascending, then -1 padding
    (ids at or past n_segments match nothing); values: [V, N] float32.
    Returns sums [V, n_segments]."""
    v, n = values.shape
    n_pad = (-n) % EVENT_TILE
    s_pad = (-n_segments) % SEG_BLOCK
    seg_ids = jnp.pad(seg_ids, (0, n_pad), constant_values=-1)
    values = jnp.pad(values, ((0, 0), (0, n_pad)))
    n_blocks = (n_segments + s_pad) // SEG_BLOCK
    sched = _schedule(seg_ids, n_blocks)

    def tile_of(s, sched):
        return _unpack(sched[s])[0]

    sums = pl.pallas_call(
        _agg_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(sched.shape[0],),
            in_specs=[
                pl.BlockSpec((ROWS, LANES),
                             lambda s, sched: (tile_of(s, sched), 0)),
                pl.BlockSpec((v, ROWS, LANES),
                             lambda s, sched: (0, tile_of(s, sched), 0)),
            ],
            out_specs=pl.BlockSpec(
                (v, SEG_BLOCK), lambda s, sched: (0, s - tile_of(s, sched))),
            scratch_shapes=[pltpu.VMEM((v, SEG_BLOCK, LANES), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((v, n_segments + s_pad), jnp.float32),
        interpret=interpret,
        name="window_agg",
    )(sched, seg_ids.reshape(-1, LANES), values.reshape(v, -1, LANES))
    return sums[:, :n_segments]
