"""Mixture-of-Experts FFN with top-k routing and capacity-bounded dispatch.

Dispatch is *sort-free and einsum-dispatch-free*: a cumsum-of-one-hot position
assignment plus scatter into per-expert buffers — O(T·E) for the position
bookkeeping and O(T·d) for data movement, never materialising the GShard
[T, E, C] dispatch tensor (intractable at E=128, T=1M).

Two distribution schedules (selected by ``moe_schedule``):

- ``tp_psum``  — activations replicated over the 'model' axis; each model
  shard owns E/|model| experts, processes every local token routed to them,
  and contributions are combined with a psum over 'model' (cost == one TP
  all-reduce of [T_local, d]).  Implemented with shard_map so dispatch
  bookkeeping stays device-local.
- ``local``    — no mesh: plain single-device dispatch (smoke tests / CPU).

(An all-to-all EP schedule — tokens sequence-split over the expert axis,
exchanged with all_to_all, computed, and combined — is the classic
alternative; for this mesh the psum schedule moves the same [T_local, d]
payload with one collective and no dispatch imbalance, so it is the one
implemented.  See EXPERIMENTS.md §Perf for the napkin comparison.)
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, MoEConfig
from repro.distributed.ctx import current_mesh, current_rules
from repro.models.layers import dense_apply, init_dense, init_mlp, mlp_apply


def init_moe(key, cfg: ArchConfig) -> dict:
    m: MoEConfig = cfg.moe
    d = cfg.d_model
    k_r, k_g, k_u, k_dn, k_s = jax.random.split(key, 5)
    dt = cfg.param_dtype
    scale = float(1.0 / np.sqrt(d))  # float(): keep bf16 weak-typed
    p = {
        "router": init_dense(k_r, d, m.n_routed, dtype=dt),
        # stacked expert weights [E, d, ff] / [E, ff, d]
        "w_gate": jax.random.normal(k_g, (m.n_routed, d, m.d_expert_ff), dtype=dt) * scale,
        "w_up": jax.random.normal(k_u, (m.n_routed, d, m.d_expert_ff), dtype=dt) * scale,
        "w_down": jax.random.normal(k_dn, (m.n_routed, m.d_expert_ff, d), dtype=dt)
        * float(1.0 / np.sqrt(m.d_expert_ff)),
    }
    if m.n_shared:
        p["shared"] = init_mlp(k_s, d, m.d_shared_ff * m.n_shared, dtype=dt)
    return p


# --------------------------------------------------------------------------
# Local (per-shard) dispatch + expert compute.
# --------------------------------------------------------------------------
def _topk_routing(router_logits: jnp.ndarray, top_k: int):
    """Returns (weights [T,k], idx [T,k]) with weights renormalised over top-k."""
    gates = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    vals, idx = jax.lax.top_k(gates, top_k)
    vals = vals / jnp.maximum(vals.sum(-1, keepdims=True), 1e-9)
    return vals, idx


def _positions_in_expert(idx: jnp.ndarray, n_expert: int):
    """idx: [T, k] expert assignment. Returns pos [T, k]: arrival order of each
    assignment within its expert (row-major over (T, k))."""
    T, k = idx.shape
    flat = idx.reshape(T * k)
    onehot = jax.nn.one_hot(flat, n_expert, dtype=jnp.int32)  # [T*k, E]
    pos = jnp.cumsum(onehot, axis=0) - 1  # position per expert
    pos = jnp.take_along_axis(pos, flat[:, None], axis=1)[:, 0]
    return pos.reshape(T, k)


def moe_ffn_local(p: dict, x: jnp.ndarray, cfg: ArchConfig, *,
                  expert_slice: Optional[tuple[int, int]] = None) -> jnp.ndarray:
    """x: [T, d] tokens (local). Computes routed-expert output.

    ``expert_slice=(start, count)``: only experts in [start, start+count) are
    computed (the caller psums partial outputs across expert shards).  Weights
    passed in ``p`` are the *local* slice when expert_slice is given.
    """
    m: MoEConfig = cfg.moe
    T, d = x.shape
    cd = cfg.compute_dtype
    logits = dense_apply(p["router"], x, jnp.float32)  # router in fp32
    weights, idx = _topk_routing(logits, m.top_k)  # [T,k]
    pos = _positions_in_expert(idx, m.n_routed)  # [T,k]
    cap = int(np.ceil(m.top_k * T * m.capacity_factor / m.n_routed))
    cap = max(cap, 1)

    e_start, e_count = expert_slice if expert_slice is not None else (0, m.n_routed)
    local_e = idx - e_start  # [T,k] index into local expert buffer
    in_shard = (local_e >= 0) & (local_e < e_count)
    keep = in_shard & (pos < cap)
    safe_e = jnp.where(keep, local_e, 0)
    safe_p = jnp.where(keep, pos, 0)

    # scatter tokens into per-expert buffers [E_loc, C, d]
    xk = jnp.broadcast_to(x[:, None, :], (T, m.top_k, d)).reshape(T * m.top_k, d)
    flat_keep = keep.reshape(-1)
    flat_e = safe_e.reshape(-1)
    flat_p = safe_p.reshape(-1)
    buf = jnp.zeros((e_count, cap, d), cd)
    buf = buf.at[flat_e, flat_p].add(
        jnp.where(flat_keep[:, None], xk.astype(cd), 0), mode="drop"
    )

    # expert GEMMs: [E,C,d] x [E,d,ff]
    g = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"].astype(cd))
    u = jnp.einsum("ecd,edf->ecf", buf, p["w_up"].astype(cd))
    h = jax.nn.silu(g) * u
    out_buf = jnp.einsum("ecf,efd->ecd", h, p["w_down"].astype(cd))

    # gather back: each (token, slot) reads its (expert, pos) row
    gathered = out_buf[flat_e, flat_p]  # [T*k, d]
    gathered = jnp.where(flat_keep[:, None], gathered, 0)
    gathered = gathered.reshape(T, m.top_k, d)
    out = jnp.einsum("tkd,tk->td", gathered.astype(jnp.float32),
                     weights.astype(jnp.float32))
    return out.astype(cd)


def _aux_load_balance_loss(logits: jnp.ndarray, idx: jnp.ndarray, n_expert: int):
    """Switch-style auxiliary loss: E * sum(fraction_tokens * router_prob)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).mean(0)
    counts = jnp.zeros((n_expert,), jnp.float32).at[idx.reshape(-1)].add(1.0)
    frac = counts / jnp.maximum(counts.sum(), 1.0)
    return n_expert * jnp.sum(frac * probs)


# --------------------------------------------------------------------------
# Distributed apply
# --------------------------------------------------------------------------
def moe_apply(p: dict, x: jnp.ndarray, cfg: ArchConfig) -> jnp.ndarray:
    """x: [B, S, d] -> [B, S, d].  Routed experts + optional shared experts."""
    m: MoEConfig = cfg.moe
    B, S, d = x.shape
    xt = x.reshape(B * S, d)

    mesh = current_mesh()
    rules = current_rules()
    model_axis = rules.rules.get("experts") if rules else None
    if mesh is not None and model_axis is not None and model_axis in mesh.axis_names \
            and mesh.shape[model_axis] > 1 and m.n_routed % mesh.shape[model_axis] == 0:
        out = _moe_tp_psum(p, xt, cfg, mesh, model_axis)
    else:
        out = moe_ffn_local(p, xt, cfg)

    out = out.reshape(B, S, d)
    if m.n_shared:
        out = out + mlp_apply(p["shared"], x, cfg.compute_dtype)
    return out


def _moe_tp_psum(p: dict, xt: jnp.ndarray, cfg: ArchConfig, mesh, model_axis: str):
    """shard_map schedule: tokens sharded over data axes (replicated over
    'model'); experts sharded over 'model'; partial outputs psum'd."""
    m: MoEConfig = cfg.moe
    rules = current_rules()
    batch_axes = rules.rules.get("batch")
    n_shards = mesh.shape[model_axis]
    e_per = m.n_routed // n_shards

    tok_spec = P(batch_axes, None)
    router_spec = jax.tree.map(lambda _: P(None, None), p["router"])
    in_specs = (
        {
            "router": router_spec,
            "w_gate": P(model_axis, None, None),
            "w_up": P(model_axis, None, None),
            "w_down": P(model_axis, None, None),
        },
        tok_spec,
    )

    def shard_fn(pl, xl):
        ax = jax.lax.axis_index(model_axis)
        out = moe_ffn_local(
            {"router": pl["router"], "w_gate": pl["w_gate"], "w_up": pl["w_up"],
             "w_down": pl["w_down"]},
            xl, cfg, expert_slice=(ax * e_per, e_per),
        )
        return jax.lax.psum(out, model_axis)

    routed = {k: p[k] for k in ("router", "w_gate", "w_up", "w_down")}
    fn = jax.shard_map(shard_fn, mesh=mesh, in_specs=in_specs,
                       out_specs=tok_spec, check_vma=False)
    return fn(routed, xt)
