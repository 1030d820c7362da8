"""Mixture-of-Experts layer: top-k routing + sort + ragged_dot expert compute.

Distribution design (DESIGN.md §5): tokens stay resident on their
(pod, data) shard; expert weights are TP-sharded on the expert-hidden dim
over the ``model`` axis and replicated over data.  Inside a shard_map the
layer (per data shard):

  1. routes tokens (softmax top-k),
  2. sorts the (token, expert-slot) stream by expert id — a *local* sort,
  3. counts tokens per expert with a bincount — **the paper's histogram**:
     the dispatch count's conflict structure is data-dependent (a
     collapsed router is the "solid image", a balanced router the
     "uniform image") and the instrumented path prices it with the
     queuing model,
  4. runs capacity-free ragged_dot expert matmuls (no token dropping),
  5. psums partial outputs over ``model`` (the intra-expert TP reduce),
  6. unsorts and combines with the top-k gate weights.

A classic whole-expert EP layout (all_to_all over an expert axis) is the
main alternative; §Perf compares the collective profiles.

The layer is scan-stackable and grad-safe (ragged_dot has transpose
rules; sort/gather transpose to scatter).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import layers


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int
    d_expert: int              # per-expert hidden (d_ff of one expert)
    num_experts: int
    top_k: int
    num_shared_experts: int = 0
    router_aux_coef: float = 0.001
    activation: str = "silu"
    dtype: str = "bfloat16"
    capacity_factor: float = 1.25   # EP path only (GShard semantics)
    bf16_combine: bool = False      # keep the EP return path (unsort +
                                    # all_to_all back + scatter) in bf16:
                                    # halves the TP-psum/a2a wire traffic;
                                    # slots are write-once so the scatter
                                    # loses no precision

    @property
    def use_ep(self) -> bool:
        """Whole-expert EP (all_to_all) for big expert counts; the small-E
        archs keep experts replicated over data and TP-shard the hidden."""
        return self.num_experts >= 64


def init(key, cfg: MoEConfig) -> dict:
    dt = jnp.dtype(cfg.dtype)
    kr, k1, k2, k3, ks = jax.random.split(key, 5)
    scale_in = cfg.d_model ** -0.5
    scale_out = cfg.d_expert ** -0.5
    p = {
        "router": layers.dense_init(kr, cfg.d_model, cfg.num_experts, dt),
        "w_gate": layers.truncated_normal_init(
            k1, (cfg.num_experts, cfg.d_model, cfg.d_expert), scale_in, dt),
        "w_up": layers.truncated_normal_init(
            k2, (cfg.num_experts, cfg.d_model, cfg.d_expert), scale_in, dt),
        "w_down": layers.truncated_normal_init(
            k3, (cfg.num_experts, cfg.d_expert, cfg.d_model), scale_out, dt),
    }
    if cfg.num_shared_experts:
        from repro.models import mlp
        p["shared"] = mlp.init(ks, cfg.d_model,
                               cfg.d_expert * cfg.num_shared_experts, dt)
    return p


def route(p: dict, x: jnp.ndarray, cfg: MoEConfig):
    """Router: returns (gates (T,k) f32, ids (T,k) i32, aux_loss scalar)."""
    logits = (x.astype(jnp.float32) @ p["router"]["w"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, cfg.top_k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance auxiliary loss.
    density = jnp.mean(
        jax.nn.one_hot(ids[..., 0], cfg.num_experts, dtype=jnp.float32),
        axis=tuple(range(ids.ndim - 1)))
    mean_probs = probs.mean(axis=tuple(range(probs.ndim - 1)))
    aux = cfg.num_experts * jnp.sum(density * mean_probs)
    return gates, ids, aux


def _expert_ffn_sorted(p: dict, xs: jnp.ndarray, group_sizes: jnp.ndarray,
                       cfg: MoEConfig, axis_name: Optional[str]):
    """ragged_dot FFN over expert-sorted rows; psum partial d_model out.

    NOTE: XLA:CPU lowers ragged_dot as an E-dense loop (every expert sees
    every row), inflating FLOPs by ~E/k; kept as an option for TPU (where
    Mosaic lowers it tightly) — the default path is the capacity-grouped
    batched matmul below.
    """
    act = {"silu": jax.nn.silu, "gelu": jax.nn.gelu}[cfg.activation]
    h = (act(jax.lax.ragged_dot(xs, p["w_gate"], group_sizes))
         * jax.lax.ragged_dot(xs, p["w_up"], group_sizes))
    y = jax.lax.ragged_dot(h.astype(xs.dtype), p["w_down"], group_sizes)
    if axis_name is not None:
        y = jax.lax.psum(y, axis_name)
    return y


def _expert_ffn_grouped(p: dict, xs: jnp.ndarray, sorted_ids: jnp.ndarray,
                        num_experts: int, capacity: int, cfg: MoEConfig,
                        axis_name: Optional[str]):
    """Capacity-grouped expert FFN: scatter expert-sorted rows into fixed
    (E, C, d) buffers, run ONE batched matmul per projection (tight FLOPs:
    E*C = Tk*cf), gather back.  Overflow rows are dropped (GShard capacity
    semantics); their combine weight contribution is zero."""
    tk, d = xs.shape
    counts = jnp.bincount(sorted_ids, length=num_experts)
    start = jnp.cumsum(counts) - counts
    pos = jnp.arange(tk) - jnp.take(start, sorted_ids)
    keep = pos < capacity
    pos_safe = jnp.where(keep, pos, capacity)       # OOB -> dropped
    buf = jnp.zeros((num_experts, capacity, d), xs.dtype)
    buf = buf.at[sorted_ids, pos_safe].set(xs, mode="drop")
    act = {"silu": jax.nn.silu, "gelu": jax.nn.gelu}[cfg.activation]
    h = act(jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])) * \
        jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    y = jnp.einsum("ecf,efd->ecd", h.astype(buf.dtype), p["w_down"])
    if axis_name is not None:
        y = jax.lax.psum(y, axis_name)
    rows = y.at[sorted_ids, pos_safe].get(mode="drop", fill_value=0.0)
    return jnp.where(keep[:, None], rows, 0.0)


def apply_local(p: dict, x: jnp.ndarray, cfg: MoEConfig,
                axis_name: Optional[str] = None):
    """MoE over local tokens x (T, d).  Runs inside shard_map (axis_name =
    TP axis to psum over) or unsharded on one device (axis_name=None).

    Returns (out (T, d), aux_loss, dispatch_ids (T*k,) expert stream in
    issue order — the instrumented profiler's index stream).
    """
    t, d = x.shape
    gates, ids, aux = route(p, x, cfg)           # (T,k)
    flat_ids = ids.reshape(-1)                   # (T*k,)
    order = jnp.argsort(flat_ids)                # local sort by expert
    xrep = jnp.repeat(x, cfg.top_k, axis=0)      # (T*k, d) slot-major
    xs = jnp.take(xrep, order, axis=0)
    sorted_ids = jnp.take(flat_ids, order)
    capacity = max(1, int(flat_ids.shape[0] / cfg.num_experts
                          * cfg.capacity_factor))
    y_sorted = _expert_ffn_grouped(p, xs, sorted_ids, cfg.num_experts,
                                   capacity, cfg, axis_name)
    inv = jnp.argsort(order)
    y = jnp.take(y_sorted, inv, axis=0).reshape(t, cfg.top_k, d)
    out = jnp.einsum("tkd,tk->td", y.astype(jnp.float32),
                     gates).astype(x.dtype)
    if cfg.num_shared_experts:
        from repro.models import mlp
        out = out + mlp.apply(p["shared"], x, cfg.activation)
    return out, aux, flat_ids


def _ep_local(p: dict, x_local: jnp.ndarray, cfg: MoEConfig,
              ep_axis: str, tp_axis: str, data_axes) -> tuple:
    """Whole-expert EP body (runs inside shard_map).

    x_local (T, d) tokens of this data shard; p holds E/D whole experts
    (TP-sharded on the expert hidden over ``tp_axis``).  GShard-style
    fixed-capacity all_to_all dispatch: per-destination-shard buffers of
    ``cap`` rows, overflow dropped (the residual path carries the token).
    The dispatch bincount is the paper's histogram — returned for the
    instrumented profiler.
    """
    d_shards = jax.lax.axis_size(ep_axis)
    t, d = x_local.shape
    e_local = cfg.num_experts // d_shards
    gates, ids, aux = route(p, x_local, cfg)            # (T,k)
    flat_ids = ids.reshape(-1)                          # (Tk,)
    tk = flat_ids.shape[0]
    order = jnp.argsort(flat_ids)
    sorted_ids = jnp.take(flat_ids, order)
    xs = jnp.take(jnp.repeat(x_local, cfg.top_k, axis=0), order, axis=0)

    cap = max(1, int(tk / d_shards * cfg.capacity_factor))
    dst = sorted_ids // e_local                         # ascending
    counts_dst = jnp.bincount(dst, length=d_shards)
    start = jnp.cumsum(counts_dst) - counts_dst
    pos_in_dst = jnp.arange(tk) - jnp.take(start, dst)
    keep = pos_in_dst < cap
    pos_safe = jnp.where(keep, pos_in_dst, cap)         # OOB -> dropped

    send_x = jnp.zeros((d_shards, cap, d), xs.dtype)
    send_x = send_x.at[dst, pos_safe].set(xs, mode="drop")
    send_id = jnp.full((d_shards, cap), e_local, jnp.int32)  # invalid
    send_id = send_id.at[dst, pos_safe].set(
        (sorted_ids % e_local).astype(jnp.int32), mode="drop")
    send_slot = jnp.full((d_shards, cap), tk, jnp.int32)     # OOB -> drop
    send_slot = send_slot.at[dst, pos_safe].set(
        order.astype(jnp.int32), mode="drop")

    recv_x = jax.lax.all_to_all(send_x, ep_axis, 0, 0, tiled=False)
    recv_id = jax.lax.all_to_all(send_id, ep_axis, 0, 0, tiled=False)
    rx = recv_x.reshape(d_shards * cap, d)
    rid = recv_id.reshape(-1)

    order2 = jnp.argsort(rid)
    rs = jnp.take(rx, order2, axis=0)
    rids = jnp.take(rid, order2)                        # invalid id=e_local
    cap2 = max(1, int(rx.shape[0] / e_local * cfg.capacity_factor))
    # invalid rows (id == e_local) scatter out-of-range -> dropped
    y = _expert_ffn_grouped(p, rs, rids, e_local, cap2, cfg, tp_axis)
    y = jnp.take(y, jnp.argsort(order2), axis=0)        # unsort locally
    comb_dt = x_local.dtype if cfg.bf16_combine else jnp.float32
    back = jax.lax.all_to_all(
        y.reshape(d_shards, cap, d).astype(comb_dt), ep_axis, 0, 0)

    y_flat = jnp.zeros((tk + 1, d), comb_dt)
    y_flat = y_flat.at[send_slot.reshape(-1)].add(
        back.reshape(-1, d).astype(comb_dt), mode="drop")
    y_tok = y_flat[:tk].reshape(t, cfg.top_k, d)
    out = jnp.einsum("tkd,tk->td", y_tok.astype(jnp.float32),
                     gates).astype(x_local.dtype)
    if cfg.num_shared_experts:
        from repro.models import mlp
        out = out + mlp.apply(p["shared"], x_local, cfg.activation)
    aux = jax.lax.pmean(aux, data_axes)
    aux = jax.lax.pmean(aux, tp_axis)
    return out, aux, flat_ids


def apply_ep(p: dict, x: jnp.ndarray, cfg: MoEConfig, mesh,
             data_axes=("pod", "data"), tp_axis: str = "model",
             ep_axis: str = "data"):
    """Whole-expert EP over `ep_axis` + intra-expert TP over `tp_axis`.

    Expert weights sharded P(ep, None, tp); tokens P(data_axes).
    Experts replicate over pod (pure DP across pods).
    """
    from jax.sharding import PartitionSpec as P

    b, s, d = x.shape

    def local_fn(p_local, x_local):
        bl, sl, _ = x_local.shape
        out, aux, disp = _ep_local(p_local, x_local.reshape(bl * sl, d),
                                   cfg, ep_axis, tp_axis, data_axes)
        return out.reshape(bl, sl, d), aux, disp

    pspec = {
        "router": {"w": P()},
        "w_gate": P(ep_axis, None, tp_axis),
        "w_up": P(ep_axis, None, tp_axis),
        "w_down": P(ep_axis, tp_axis, None),
    }
    if cfg.num_shared_experts:
        pspec["shared"] = {"w_gate": P(None, tp_axis),
                           "w_up": P(None, tp_axis),
                           "w_down": P(tp_axis, None)}
    out, aux, disp = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(pspec, P(data_axes)),
        out_specs=(P(data_axes), P(), P(data_axes)),
    )(p, x)
    return out, aux, disp


def apply_sharded(p: dict, x: jnp.ndarray, cfg: MoEConfig, mesh,
                  data_axes=("pod", "data"), tp_axis: str = "model"):
    """shard_map wrapper: x (B, S, d) batch-sharded; experts TP-sharded.

    Used by the big-model train/serve steps; smoke tests use apply_local.
    """
    from jax.sharding import PartitionSpec as P

    b, s, d = x.shape

    def local_fn(p_local, x_local):
        bl, sl, _ = x_local.shape
        out, aux, disp = apply_local(
            p_local, x_local.reshape(bl * sl, d), cfg, axis_name=tp_axis)
        aux = jax.lax.pmean(aux, data_axes)
        aux = jax.lax.pmean(aux, tp_axis)
        return out.reshape(bl, sl, d), aux, disp

    pspec = {
        "router": {"w": P()},
        "w_gate": P(None, None, tp_axis),
        "w_up": P(None, None, tp_axis),
        "w_down": P(None, tp_axis, None),
    }
    if cfg.num_shared_experts:
        pspec["shared"] = {"w_gate": P(None, tp_axis),
                           "w_up": P(None, tp_axis),
                           "w_down": P(tp_axis, None)}
    out, aux, disp = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(pspec, P(data_axes)),
        out_specs=(P(data_axes), P(), P(data_axes)),
    )(p, x)
    return out, aux, disp
