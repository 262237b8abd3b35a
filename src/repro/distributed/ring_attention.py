"""Ring attention (shard_map): sequence-parallel exact attention for the
collective-bound prefill cells (§Roofline future-work item, implemented).

Q, K, V are sequence-sharded over the TP axis.  Each step computes local
attention against the currently-held KV block while `jax.lax.ppermute`
rotates KV around the ring; online-softmax statistics merge the blocks.
Per-chip wire bytes = (n-1)/n * |KV| — the same volume a single all-gather
of KV would move — but peak memory never holds the full KV, and on real
hardware each hop overlaps with the local block's compute (the point of
Ring Attention; our dry-run scores the wire bytes, the overlap is a latency
property).

Causal masking works on absolute positions carried with each block, so the
math is exact for causal prefill, at the cost of idle hops for fully-masked
blocks (the load-imbalance fix of striped/zigzag variants is noted as
future work).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

NEG_INF = -1e30


def _local_block(q, k, v, q_pos, kv_pos, causal, scale):
    """q: [B,Sq,KV,G,D]; k,v: [B,Skv,KV,D] -> (scores-weighted acc, m, l)."""
    s = jnp.einsum("bqkgd,bpkd->bkgqp", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        mask = kv_pos[None, :] <= q_pos[:, None]
        s = jnp.where(mask, s, NEG_INF)
    m = s.max(axis=-1)  # [B,KV,G,Sq]
    p = jnp.exp(s - m[..., None])
    l = p.sum(axis=-1)
    acc = jnp.einsum("bkgqp,bpkd->bqkgd", p, v.astype(jnp.float32))
    return acc, m, l


def ring_attention(q, k, v, *, mesh: Mesh, axis: str = "model",
                   causal: bool = True, dp_axes=("data",)):
    """q: [B, S, KV, G, D]; k, v: [B, S, KV, D]; S sharded over `axis`.

    Returns [B, S, KV, G, D] with the same sharding as q.
    """
    n = mesh.shape[axis]
    B, S, KVH, G, D = q.shape
    scale = 1.0 / np.sqrt(D)
    dp = tuple(a for a in dp_axes if a in mesh.axis_names) or None

    q_spec = P(dp, axis, None, None, None)
    kv_spec = P(dp, axis, None, None)

    def ring(ql, kl, vl):
        idx = jax.lax.axis_index(axis)
        s_loc = ql.shape[1]
        q_pos = idx * s_loc + jnp.arange(s_loc)

        m0 = jnp.full((B and ql.shape[0], KVH, G, s_loc), NEG_INF, jnp.float32)
        l0 = jnp.zeros_like(m0)
        a0 = jnp.zeros(ql.shape[:1] + (s_loc, KVH, G, D), jnp.float32)

        def body(i, carry):
            m, l, acc, kb, vb = carry
            src = (idx - i) % n  # whose KV block we currently hold
            kv_pos = src * s_loc + jnp.arange(s_loc)
            a_i, m_i, l_i = _local_block(ql, kb, vb, q_pos, kv_pos, causal,
                                         scale)
            m_new = jnp.maximum(m, m_i)
            alpha = jnp.exp(m - m_new)
            beta = jnp.exp(m_i - m_new)
            l = l * alpha + l_i * beta
            acc = (acc * alpha.transpose(0, 3, 1, 2)[..., None]
                   + a_i * beta.transpose(0, 3, 1, 2)[..., None])
            # rotate KV one hop around the ring
            perm = [(j, (j + 1) % n) for j in range(n)]
            kb = jax.lax.ppermute(kb, axis, perm)
            vb = jax.lax.ppermute(vb, axis, perm)
            return m_new, l, acc, kb, vb

        m, l, acc, _, _ = jax.lax.fori_loop(0, n, body, (m0, l0, a0, kl, vl))
        l = jnp.maximum(l, 1e-30)
        out = acc / l.transpose(0, 3, 1, 2)[..., None]
        return out.astype(q.dtype)

    fn = jax.shard_map(ring, mesh=mesh,
                       in_specs=(q_spec, kv_spec, kv_spec),
                       out_specs=q_spec, check_vma=False)
    return fn(q, k, v)


def ring_attention_ref(q, k, v, *, causal: bool = True):
    """Single-device oracle (same math as models.attention naive path)."""
    B, S, KVH, G, D = q.shape
    pos = jnp.arange(S)
    acc, m, l = _local_block(q, k, v, pos, pos, causal, 1.0 / np.sqrt(D))
    l = jnp.maximum(l, 1e-30)
    return (acc / l.transpose(0, 3, 1, 2)[..., None]).astype(q.dtype)
