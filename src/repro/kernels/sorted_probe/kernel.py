"""Pallas TPU kernel: batched probe of a sorted run (the LSM read hot spot).

TPU adaptation of RocksDB's per-key binary search: binary search is a
scalar, branch-heavy loop, hostile to the VPU.  Instead each (query block
x table tile) cell compares every query with every table entry and counts
``rank += #{entries < q}`` — an O(T) but fully vectorized rank.

The TPU has no 64-bit integer lanes, so every int64 key arrives as two
int32 words ``(hi, lo)`` whose signed lexicographic order is the key order
(``ops.split_keys``).  Table words are laid out ``(rows, 128)``; query
words are lane-replicated ``(QUERY_BLOCK, 128)``, so one table row
broadcast over the sublanes meets 128 entries x every query in a single
elementwise compare.  Per-lane partial counts accumulate in the output
block across the sequential table-tile axis and are summed over lanes
outside the kernel.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
QUERY_BLOCK = 256
TABLE_TILE = 8192


def _probe_kernel(thi_ref, tlo_ref, qhi_ref, qlo_ref, lt_ref, eq_ref):
    @pl.when(pl.program_id(1) == 0)            # first table tile
    def _init():
        lt_ref[...] = jnp.zeros_like(lt_ref)
        eq_ref[...] = jnp.zeros_like(eq_ref)

    qh, ql = qhi_ref[...], qlo_ref[...]        # [QB, 128], lane-replicated
    lt, eq = lt_ref[...], eq_ref[...]
    for r in range(TABLE_TILE // LANES):       # one 128-entry table row each
        th, tl = thi_ref[r:r + 1, :], tlo_ref[r:r + 1, :]     # [1, 128]
        hi_eq = th == qh
        lt += ((th < qh) | (hi_eq & (tl < ql))).astype(jnp.int32)
        eq += (hi_eq & (tl == ql)).astype(jnp.int32)
    lt_ref[...] = lt
    eq_ref[...] = eq


@partial(jax.jit, static_argnames=("interpret",))
def sorted_probe(t_hi: jax.Array, t_lo: jax.Array, q_hi: jax.Array,
                 q_lo: jax.Array, *, interpret: bool):
    """Rank of each query key in a sorted table, on split int32 words.

    t_hi/t_lo: [T] words of an ascending table, T a multiple of
    TABLE_TILE; q_hi/q_lo: [N], N a multiple of QUERY_BLOCK (``ops.probe``
    pads both).  Returns (pos [N] int32, found [N] bool): pos counts the
    entries below the query (its insertion point, == the match index where
    found)."""
    t, n = t_hi.shape[0], q_hi.shape[0]
    assert t % TABLE_TILE == 0 and n % QUERY_BLOCK == 0, (t, n)
    t_hi, t_lo = (w.reshape(-1, LANES) for w in (t_hi, t_lo))
    q_hi, q_lo = (jnp.broadcast_to(w[:, None], (n, LANES))
                  for w in (q_hi, q_lo))
    table_spec = pl.BlockSpec((TABLE_TILE // LANES, LANES),
                              lambda i, j: (j, 0))
    query_spec = pl.BlockSpec((QUERY_BLOCK, LANES), lambda i, j: (i, 0))
    lt, eq = pl.pallas_call(
        _probe_kernel,
        grid=(n // QUERY_BLOCK, t // TABLE_TILE),
        in_specs=[table_spec, table_spec, query_spec, query_spec],
        out_specs=[query_spec, query_spec],
        out_shape=[jax.ShapeDtypeStruct((n, LANES), jnp.int32)] * 2,
        interpret=interpret,
        name="sorted_probe",
    )(t_hi, t_lo, q_hi, q_lo)
    return jnp.sum(lt, axis=1), jnp.sum(eq, axis=1) > 0
