"""Ring attention: sequence/context parallelism over a mesh axis.

The reference has NO sequence parallelism (SURVEY §2.3 — its long-sequence
story is LoD batching); this is a required TPU-native capability upgrade:
shard the TIME dimension of attention across devices and rotate key/value
blocks around the ring with ``lax.ppermute`` while accumulating
flash-attention-style online-softmax partials. Communication overlaps
compute block-by-block; memory per device is O(T/P), enabling sequences P×
longer than a single chip could hold.

Works on any mesh axis (ICI ring on TPU; verified on the CPU test mesh).
"""

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["ring_attention", "dense_attention"]


def dense_attention(q, k, v, causal=False, scale=None):
    """Reference single-device attention. q,k,v: [B, T, H, D]."""
    scale = scale or (q.shape[-1] ** -0.5)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        t = q.shape[1]
        mask = jnp.tril(jnp.ones((t, t), bool))
        s = jnp.where(mask, s, jnp.finfo(s.dtype).min)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _ring_body(q, k, v, axis_name, n_shards, causal, scale):
    """Per-shard body: q,k,v local [B, Tc, H, D]."""
    b, tc, h, d = q.shape
    idx = jax.lax.axis_index(axis_name)
    q_pos = idx * tc + jnp.arange(tc)          # global query positions
    neg = jnp.asarray(-1e30, jnp.float32)

    m0 = jnp.full((b, h, tc), neg, jnp.float32)
    l0 = jnp.zeros((b, h, tc), jnp.float32)
    acc0 = jnp.zeros((b, tc, h, d), jnp.float32)

    def step(i, carry):
        k_blk, v_blk, m, l, acc = carry
        src = (idx + i) % n_shards             # owner of the block we hold
        k_pos = src * tc + jnp.arange(tc)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k_blk,
                       preferred_element_type=jnp.float32) * scale
        if causal:
            mask = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(mask[None, None], s, neg)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # guard fully-masked rows (m_new = -inf): contribute nothing
        safe_m = jnp.where(m_new <= neg / 2, 0.0, m_new)
        p = jnp.exp(jnp.where(m_new[..., None] <= neg / 2, neg,
                              s - safe_m[..., None]))
        corr = jnp.exp(jnp.where(m <= neg / 2, neg, m - safe_m))
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr.transpose(0, 2, 1)[..., None] + jnp.einsum(
            "bhqk,bkhd->bqhd", p, v_blk.astype(jnp.float32))
        k_nxt, v_nxt = jax.lax.ppermute(
            (k_blk, v_blk), axis_name,
            [(j, (j - 1) % n_shards) for j in range(n_shards)])
        return (k_nxt, v_nxt, m, l, acc), (m_new,)

    carry = (k, v, m0, l0, acc0)
    for i in range(n_shards):
        (k_c, v_c, m, l, acc), (m_new,) = step(i, carry)
        carry = (k_c, v_c, m_new, l, acc)
    _, _, m, l, acc = carry
    out = acc / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ring_attention(q, k, v, mesh, axis_name="sp", causal=False,
                   scale=None):
    """q,k,v: [B, T, H, D] sharded (or shardable) on T over ``axis_name``.
    Returns [B, T, H, D] with the same sharding. Differentiable (the body
    is pure jnp + ppermute, both transposable)."""
    scale = scale or (q.shape[-1] ** -0.5)
    n_shards = mesh.shape[axis_name]
    spec = P(None, axis_name, None, None)
    fn = shard_map(
        functools.partial(_ring_body, axis_name=axis_name,
                          n_shards=n_shards, causal=causal, scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)
