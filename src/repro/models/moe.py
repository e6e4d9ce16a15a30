"""Token-choice top-k MoE with grouped, capacity-bounded dispatch.

GShard-style routing shaped for GSPMD on a ("data", "model") mesh:

  * routing groups = batch rows (GShard's "groups"); every group sorts and
    capacity-drops its own tokens, so all dispatch tensors keep a leading
    batch axis sharded over "data" — nothing re-materializes at global size;
  * expert weights are stacked (E, ...) and sharded on E over "model"
    (expert parallelism); experts are zero-padded to a multiple of the EP
    degree and the router never routes to padding;
  * `shard_axes` (set by the launch layer) adds with_sharding_constraint on
    the (B, E, C, d) dispatch buffers so XLA places the data->expert
    all-to-all exactly once per direction.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import layers


@dataclasses.dataclass(frozen=True)
class MoeSpec:
    d_model: int
    d_ff: int                  # per-expert hidden
    n_experts: int
    top_k: int
    capacity_factor: float = 1.25
    ep_pad_to: int = 1         # pad experts to a multiple of this
    # activation sharding (None = no constraints; set by launch layer)
    batch_axes: Optional[tuple] = None
    ep_axis: Optional[str] = None
    # "einsum" (GSPMD auto) | "shard_a2a" (shard_map: local dispatch to the
    # shard's own experts + ONE psum combine — see moe_fwd_sharded)
    impl: str = "einsum"
    mesh: Optional[object] = None  # required for impl="shard_a2a"

    @property
    def padded_experts(self) -> int:
        return -(-self.n_experts // self.ep_pad_to) * self.ep_pad_to

    def capacity(self, group_tokens: int) -> int:
        cap = int(self.capacity_factor * group_tokens * self.top_k
                  / self.n_experts)
        return max(4, -(-cap // 4) * 4)


def moe_params(key, spec: MoeSpec, dtype, abstract: bool):
    e = spec.padded_experts
    scale = 1.0 / math.sqrt(spec.d_model)
    ks = jax.random.split(key, 4) if not abstract else [None] * 4
    return {
        "router": layers.make_param(ks[0], (spec.d_model, e), dtype, scale,
                                    abstract),
        "w_gate": layers.make_param(ks[1], (e, spec.d_model, spec.d_ff),
                                    dtype, scale, abstract),
        "w_up": layers.make_param(ks[2], (e, spec.d_model, spec.d_ff),
                                  dtype, scale, abstract),
        "w_down": layers.make_param(ks[3], (e, spec.d_ff, spec.d_model),
                                    dtype, 1.0 / math.sqrt(spec.d_ff),
                                    abstract),
    }


def _constrain(x, spec: MoeSpec, parts):
    if spec.batch_axes is None:
        return x
    return jax.lax.with_sharding_constraint(x, P(*parts))


def moe_fwd(p, x, spec: MoeSpec):
    if spec.impl == "shard_a2a" and spec.mesh is not None:
        return moe_fwd_sharded(p, x, spec)
    return moe_fwd_einsum(p, x, spec)


def _dispatch_compute(p, x, gate_w, gate_i, e_lo, n_loc: int, cap: int,
                      spec: MoeSpec):
    """Capacity-bounded dispatch of (B, S, d) tokens to experts
    [e_lo, e_lo + n_loc) of the stacked weights p (already sliced to this
    range), combined with gate weights.  Pure local computation.
    ``e_lo`` may be traced (axis_index); ``n_loc`` is static."""
    b, s, d = x.shape
    flat_e = gate_i.reshape(b, s * spec.top_k)
    flat_t = jnp.tile(jnp.repeat(jnp.arange(s), spec.top_k)[None], (b, 1))
    flat_w = gate_w.reshape(b, s * spec.top_k)
    mine = (flat_e >= e_lo) & (flat_e < e_lo + n_loc)
    loc_e = jnp.where(mine, flat_e - e_lo, n_loc)  # n_loc = drop bucket
    order = jnp.argsort(loc_e, axis=1, stable=True)
    se = jnp.take_along_axis(loc_e, order, 1)
    st = jnp.take_along_axis(flat_t, order, 1)
    sw = jnp.take_along_axis(flat_w, order, 1)
    idx = jnp.arange(s * spec.top_k)[None]
    same = jnp.concatenate(
        [jnp.zeros((b, 1), jnp.int32),
         (se[:, 1:] == se[:, :-1]).astype(jnp.int32)], 1)
    seg_start = jnp.where(same == 0, idx, 0)
    seg_start = jax.lax.associative_scan(jnp.maximum, seg_start, axis=1)
    seg_pos = idx - seg_start
    keep = (seg_pos < cap) & (se < n_loc)
    buf_slot = jnp.where(keep, se * cap + seg_pos, n_loc * cap)

    gathered = jnp.take_along_axis(x, st[..., None], axis=1)
    buffers = jnp.zeros((b, n_loc * cap + 1, d), x.dtype)
    buffers = jax.vmap(lambda bf, sl, g: bf.at[sl].set(g))(
        buffers, buf_slot, gathered)
    buffers = buffers[:, :-1].reshape(b, n_loc, cap, d)

    h_g = jax.nn.silu(jnp.einsum("becd,edf->becf", buffers, p["w_gate"]))
    h_u = jnp.einsum("becd,edf->becf", buffers, p["w_up"])
    h = jnp.einsum("becf,efd->becd", h_g * h_u, p["w_down"])

    flat_out = h.reshape(b, n_loc * cap, d)
    safe_slot = jnp.minimum(buf_slot, n_loc * cap - 1)
    contrib = jnp.take_along_axis(flat_out, safe_slot[..., None], axis=1)
    contrib = jnp.where(keep[..., None], contrib, 0.0) * sw[..., None]
    out = jnp.zeros((b, s, d), x.dtype)
    return jax.vmap(lambda o, t, c: o.at[t].add(c))(out, st, contrib)


def moe_fwd_sharded(p, x, spec: MoeSpec):
    """shard_map MoE: tokens are data-sharded and model-replicated, so each
    expert-parallel shard locally selects the (token, k) pairs routed to its
    own expert slice — dispatch costs ZERO communication — computes them, and
    the combine is ONE psum of the (B_loc, S, d) output over the EP axis
    (exactly a dense-TP all-reduce).  Replaces the einsum formulation's
    gather/scatter all-reduces of (B, E, C, d) buffers (~16x the bytes).
    """
    mesh, ep = spec.mesh, spec.ep_axis
    ba = spec.batch_axes or ()
    e = spec.padded_experts
    n_shards = mesh.shape[ep]
    e_loc = e // n_shards
    b, s, d = x.shape
    cap = spec.capacity(s)

    logits = jnp.einsum("bsd,de->bse", x, p["router"]).astype(jnp.float32)
    if e != spec.n_experts:
        pad_mask = jnp.arange(e) >= spec.n_experts
        logits = jnp.where(pad_mask[None, None, :], -jnp.inf, logits)
    gate_w, gate_i = jax.lax.top_k(logits, spec.top_k)
    gate_w = jax.nn.softmax(gate_w, axis=-1).astype(x.dtype)

    probs = jax.nn.softmax(logits, axis=-1)
    onehot1 = jax.nn.one_hot(gate_i[..., 0], e, dtype=jnp.float32)
    aux = spec.n_experts * jnp.mean(
        jnp.mean(onehot1, axis=1) * jnp.mean(probs, axis=1))

    tok_spec = P(ba, None, None)
    route_spec = P(ba, None, None)
    w_spec = {"w_gate": P(ep, None, None), "w_up": P(ep, None, None),
              "w_down": P(ep, None, None)}

    def local(weights, x_loc, gw, gi):
        my = jax.lax.axis_index(ep)
        out = _dispatch_compute(weights, x_loc, gw, gi,
                                my * e_loc, e_loc, cap, spec)
        return jax.lax.psum(out, ep)

    out = jax.shard_map(
        local, mesh=mesh,
        in_specs=(w_spec, tok_spec, route_spec, route_spec),
        out_specs=tok_spec, check_vma=False,
    )({k: p[k] for k in ("w_gate", "w_up", "w_down")}, x, gate_w, gate_i)
    return out, aux


def moe_fwd_einsum(p, x, spec: MoeSpec):
    """x: (B, S, d) -> (B, S, d) + aux loss. Each batch row is a group."""
    b, s, d = x.shape
    e = spec.padded_experts
    cap = spec.capacity(s)
    ba = spec.batch_axes
    ep = spec.ep_axis

    logits = jnp.einsum("bsd,de->bse", x, p["router"]).astype(jnp.float32)
    if e != spec.n_experts:
        pad_mask = jnp.arange(e) >= spec.n_experts
        logits = jnp.where(pad_mask[None, None, :], -jnp.inf, logits)
    gate_w, gate_i = jax.lax.top_k(logits, spec.top_k)     # (B, S, K)
    gate_w = jax.nn.softmax(gate_w, axis=-1).astype(x.dtype)

    probs = jax.nn.softmax(logits, axis=-1)
    onehot1 = jax.nn.one_hot(gate_i[..., 0], e, dtype=jnp.float32)
    aux = spec.n_experts * jnp.mean(
        jnp.mean(onehot1, axis=1) * jnp.mean(probs, axis=1))

    # ---- per-group (per batch row) sort-based dispatch -------------------
    flat_e = gate_i.reshape(b, s * spec.top_k)
    flat_t = jnp.tile(jnp.repeat(jnp.arange(s), spec.top_k)[None], (b, 1))
    flat_w = gate_w.reshape(b, s * spec.top_k)
    order = jnp.argsort(flat_e, axis=1, stable=True)
    se = jnp.take_along_axis(flat_e, order, 1)
    st = jnp.take_along_axis(flat_t, order, 1)
    sw = jnp.take_along_axis(flat_w, order, 1)
    idx = jnp.arange(s * spec.top_k)[None]
    same = jnp.concatenate(
        [jnp.zeros((b, 1), jnp.int32),
         (se[:, 1:] == se[:, :-1]).astype(jnp.int32)], 1)
    seg_start = jnp.where(same == 0, idx, 0)
    seg_start = jax.lax.associative_scan(jnp.maximum, seg_start, axis=1)
    seg_pos = idx - seg_start
    keep = seg_pos < cap
    buf_slot = jnp.where(keep, se * cap + seg_pos, e * cap)   # e*cap = drop

    gathered = jnp.take_along_axis(x, st[..., None], axis=1)  # (B, S*K, d)
    buffers = jnp.zeros((b, e * cap + 1, d), x.dtype)
    buffers = jax.vmap(lambda bf, sl, g: bf.at[sl].set(g))(
        buffers, buf_slot, gathered)
    buffers = buffers[:, :-1].reshape(b, e, cap, d)
    buffers = _constrain(buffers, spec, (ba, ep, None, None))

    h_g = jax.nn.silu(jnp.einsum("becd,edf->becf", buffers, p["w_gate"]))
    h_u = jnp.einsum("becd,edf->becf", buffers, p["w_up"])
    h = jnp.einsum("becf,efd->becd", h_g * h_u, p["w_down"])
    h = _constrain(h, spec, (ba, ep, None, None))

    flat_out = h.reshape(b, e * cap, d)
    safe_slot = jnp.minimum(buf_slot, e * cap - 1)
    contrib = jnp.take_along_axis(flat_out, safe_slot[..., None], axis=1)
    contrib = jnp.where(keep[..., None], contrib, 0.0) * sw[..., None]
    out = jnp.zeros((b, s, d), x.dtype)
    out = jax.vmap(lambda o, t, c: o.at[t].add(c))(out, st, contrib)
    out = _constrain(out, spec, (ba, None, None))
    return out, aux


__all__ = ["MoeSpec", "moe_params", "moe_fwd", "moe_fwd_einsum",
           "moe_fwd_sharded"]
