"""Mixture-of-Experts block: sort-free capacity-bounded routing under shard_map.

Design (see DESIGN.md §6): activations are data-sharded and replicated over
the ``model`` axis; expert weights are either

* ``moe_shard="expert"`` — experts sharded over ``model`` (expert parallelism,
  llama4: 128 experts / 16 shards).  Each mesh cell routes its row's tokens to
  *its local experts only* (gather into a capacity buffer), runs the expert
  FFNs, and the per-cell partial outputs are combined with one ``psum`` over
  ``model`` — the same reduction a TP dense FFN needs, so no extra collective
  class is introduced.
* ``moe_shard="ffn"`` — every expert on every shard with its hidden dim
  TP-sharded (mixtral: 8 experts < 16 shards would waste half the axis under
  EP).  Same psum combine.

Routing is capacity-bounded with silent drops (MaxText-style "dropping" MoE);
rank-within-expert is computed with a cumsum over a [tokens, E_local] one-hot,
which never materializes a [T, E, C] dispatch tensor in the HLO.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.models.config import ArchConfig
from repro.models.layers import cdiv
from repro.models.sharding import MeshCtx


def _route_local(x, router, n_local_experts, expert_offset, cfg: ArchConfig):
    """Token->local-expert assignment with capacity bound.

    x: [N, D]; returns (buf [E_loc*C+1, D], flat_pos [N, K], gates [N, K]).
    The last buffer row is the drop bin.
    """
    n, d = x.shape
    k = cfg.top_k
    logits = jnp.einsum("nd,de->ne", x.astype(jnp.float32),
                        router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, sel = lax.top_k(probs, k)                      # [N, K]
    gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)

    cap = max(1, cdiv(int(n * k * cfg.capacity_factor), cfg.num_experts))
    local = sel - expert_offset                           # [N, K]
    mine = (local >= 0) & (local < n_local_experts)
    local_c = jnp.where(mine, local, 0)

    # rank of each (token, k) assignment within its expert, in token order
    onehot = (jax.nn.one_hot(local_c, n_local_experts, dtype=jnp.int32)
              * mine[..., None].astype(jnp.int32))       # [N, K, E_loc]
    flat_oh = onehot.reshape(n * k, n_local_experts)
    ranks = (jnp.cumsum(flat_oh, axis=0) - flat_oh)       # exclusive cumsum
    rank = jnp.sum(ranks * flat_oh, axis=-1).reshape(n, k)

    keep = mine & (rank < cap)
    flat_pos = jnp.where(keep, local_c * cap + rank, n_local_experts * cap)

    buf = jnp.zeros((n_local_experts * cap + 1, d), x.dtype)
    xk = jnp.broadcast_to(x[:, None], (n, k, d)).reshape(n * k, d)
    buf = buf.at[flat_pos.reshape(-1)].add(xk, mode="drop")
    return buf, flat_pos, gates.astype(x.dtype), cap


def moe_ffn(params: dict, x: jax.Array, cfg: ArchConfig, mctx: MeshCtx
            ) -> jax.Array:
    """x: [B, S, D] (sharded P(dp, None, None)); returns same shape/sharding."""
    b, s, d = x.shape
    if cfg.moe_shard == "2d" and b % mctx.dp_size == 0 \
            and mctx.mesh.devices.size > 1:
        return moe_ffn_2d(params, x, cfg, mctx)
    tp = mctx.tp
    # batch=1 decode cells can't split tokens over dp — replicate instead
    dp = mctx.dp if b % mctx.dp_size == 0 else None
    ep = cfg.moe_shard == "expert"
    e = cfg.num_experts

    if ep:
        w_spec = P(tp, None, None)        # experts sharded
        sh_spec = P(None, tp)             # shared expert: TP on hidden dim
    else:
        w_spec = P(None, None, tp)        # hidden dim sharded
        sh_spec = P(None, tp)
    w_spec_out = P(tp, None, None) if ep else P(None, tp, None)

    in_specs = [P(dp, None, None), P(None, None),
                w_spec, w_spec, w_spec_out]
    args = [x, params["router"], params["wg"], params["wu"], params["wo"]]
    if cfg.shared_expert:
        in_specs += [sh_spec, sh_spec, P(tp, None)]
        args += [params["sh_wg"], params["sh_wu"], params["sh_wo"]]

    def local_fn(x_loc, router, wg, wu, wo, *shared):
        nloc = x_loc.shape[0] * x_loc.shape[1]
        xf = x_loc.reshape(nloc, d)
        if ep:
            e_loc = wg.shape[0]
            off = lax.axis_index(tp) * e_loc
        else:
            e_loc, off = e, 0
        buf, flat_pos, gates, cap = _route_local(xf, router, e_loc, off, cfg)
        buf_e = buf[:-1].reshape(e_loc, cap, d)
        h = jnp.einsum("ecd,edf->ecf", buf_e, wg.astype(buf_e.dtype))
        u = jnp.einsum("ecd,edf->ecf", buf_e, wu.astype(buf_e.dtype))
        out_e = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * u,
                           wo.astype(buf_e.dtype))
        out_flat = jnp.concatenate(
            [out_e.reshape(e_loc * cap, d), jnp.zeros((1, d), out_e.dtype)], 0)
        gathered = out_flat[flat_pos.reshape(-1)].reshape(nloc, cfg.top_k, d)
        y = jnp.sum(gathered * gates[..., None], axis=1)
        if shared:
            swg, swu, swo = shared
            g = jnp.einsum("nd,df->nf", xf, swg.astype(xf.dtype))
            uu = jnp.einsum("nd,df->nf", xf, swu.astype(xf.dtype))
            y = y + jnp.einsum("nf,fd->nd", jax.nn.silu(g) * uu,
                               swo.astype(xf.dtype))
        y = lax.psum(y, tp)
        return y.reshape(x_loc.shape)

    return jax.shard_map(local_fn, mesh=mctx.mesh, in_specs=tuple(in_specs),
                         out_specs=P(dp, None, None), check_vma=False)(*args)


def moe_ffn_2d(params: dict, x: jax.Array, cfg: ArchConfig, mctx: MeshCtx
               ) -> jax.Array:
    """Fully-sharded expert weights (E over model x F over dp) with token
    movement instead of weight movement (§Perf cell B).

    Decode steps carry ~KBs of activations but EP+FSDP weight-gathering moves
    ~GBs of expert weights per step; here every cell all-gathers the token
    batch over dp (tiny), runs its (E_loc, F_loc) weight shard, and one psum
    over (model, dp) completes both partial dims.  Intended for serving
    (small token counts); training keeps the "expert"/"ffn" modes.
    """
    b, s, d = x.shape
    tp = mctx.tp
    dp = mctx.dp
    e, k = cfg.num_experts, cfg.top_k

    in_specs = [P(dp, None, None), P(None, None),
                P(tp, None, dp), P(tp, None, dp), P(tp, dp, None)]
    args = [x, params["router"], params["wg"], params["wu"], params["wo"]]
    if cfg.shared_expert:
        in_specs += [P(dp, tp), P(dp, tp), P(tp, None)]
        args += [params["sh_wg"], params["sh_wu"], params["sh_wo"]]

    dp_size = mctx.dp_size

    def local_fn(x_loc, router, wg, wu, wo, *shared):
        # gather the token batch over dp (tiny for decode)
        x_all = lax.all_gather(x_loc, dp, axis=0, tiled=True)  # [B, S, D]
        n = x_all.shape[0] * x_all.shape[1]
        xf = x_all.reshape(n, d)
        e_loc = wg.shape[0]
        off = lax.axis_index(tp) * e_loc
        buf, flat_pos, gates, cap = _route_local(xf, router, e_loc, off, cfg)
        buf_e = buf[:-1].reshape(e_loc, cap, d)
        h = jnp.einsum("ecd,edf->ecf", buf_e, wg.astype(buf_e.dtype))
        u = jnp.einsum("ecd,edf->ecf", buf_e, wu.astype(buf_e.dtype))
        out_e = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * u,
                           wo.astype(buf_e.dtype))
        out_flat = jnp.concatenate(
            [out_e.reshape(e_loc * cap, d), jnp.zeros((1, d), out_e.dtype)], 0)
        gathered = out_flat[flat_pos.reshape(-1)].reshape(n, k, d)
        y = jnp.sum(gathered * gates[..., None], axis=1)
        if shared:
            # shared expert: D sharded over dp, F over tp; finish the dp
            # partial-sum before the nonlinearity, then pre-scale by 1/dp
            # so the joint (tp, dp) psum below stays exact
            swg, swu, swo = shared
            row = lax.axis_index(dp[0])
            for ax in dp[1:]:
                row = row * lax.axis_size(ax) + lax.axis_index(ax)
            d_loc = swg.shape[0]
            xs = lax.dynamic_slice(xf, (0, row * d_loc), (n, d_loc))
            g = lax.psum(jnp.einsum("nd,df->nf", xs, swg.astype(xs.dtype)),
                         dp)
            uu = lax.psum(jnp.einsum("nd,df->nf", xs, swu.astype(xs.dtype)),
                          dp)
            y_sh = jnp.einsum("nf,fd->nd", jax.nn.silu(g) * uu,
                              swo.astype(xs.dtype))
            y = y + y_sh / dp_size
        y = lax.psum(y, (tp,) + tuple(dp))
        # return this cell's dp slice of the token batch
        row = lax.axis_index(dp[0])
        for ax in dp[1:]:
            row = row * lax.axis_size(ax) + lax.axis_index(ax)
        b_loc = b // dp_size
        y = y.reshape(b, s, d)
        return lax.dynamic_slice(y, (row * b_loc, 0, 0), (b_loc, s, d))

    return jax.shard_map(local_fn, mesh=mctx.mesh, in_specs=tuple(in_specs),
                         out_specs=P(dp, None, None), check_vma=False)(*args)


def moe_param_shapes(cfg: ArchConfig, n_layers: int) -> dict:
    """Abstract shapes for one stacked MoE-FFN group ([L, ...] leaves)."""
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    shapes = {
        "router": (n_layers, d, e),
        "wg": (n_layers, e, d, f),
        "wu": (n_layers, e, d, f),
        "wo": (n_layers, e, f, d),
    }
    if cfg.shared_expert:
        shapes |= {"sh_wg": (n_layers, d, f), "sh_wu": (n_layers, d, f),
                   "sh_wo": (n_layers, f, d)}
    return shapes


def moe_param_specs(cfg: ArchConfig, dp=("data",)) -> dict:
    """PartitionSpecs for stacked MoE params (leading layer dim unsharded).

    EP mode shards experts over ``model`` AND FSDP-shards the d_model dim over
    ``dp`` (gathered per layer inside the scan, like every other weight) —
    without the dp factor a 400B MoE puts ~48 GB/chip of expert weights on
    each device.
    """
    if cfg.moe_shard == "2d":       # E over model, F over dp: no gathers
        w = P(None, "model", None, dp)
        wo = P(None, "model", dp, None)
        specs = {"router": P(None, None, None), "wg": w, "wu": w, "wo": wo}
        if cfg.shared_expert:
            specs |= {"sh_wg": P(None, dp, "model"),
                      "sh_wu": P(None, dp, "model"),
                      "sh_wo": P(None, "model", dp)}
        return specs
    ep = cfg.moe_shard == "expert"
    w = P(None, "model", dp, None) if ep else P(None, None, dp, "model")
    wo = P(None, "model", None, dp) if ep else P(None, None, "model", dp)
    specs = {"router": P(None, None, None), "wg": w, "wu": w, "wo": wo}
    if cfg.shared_expert:
        specs |= {"sh_wg": P(None, dp, "model"),
                  "sh_wu": P(None, dp, "model"),
                  "sh_wo": P(None, "model", dp)}
    return specs
