"""Ring attention: sequence/context parallelism over the 'seq' mesh axis.

Reference capability: NONE — SURVEY.md §5 "Long-context" records that the
reference has no sequence parallelism (TBPTT only); this is the additive
TPU-native answer it prescribes: shard the sequence axis across devices,
rotate K/V blocks around the ring with ppermute while accumulating
flash-style online softmax, so attention memory per device is O(T/n) and
the K/V transfer overlaps with compute on ICI neighbors.

Layout: q, k, v are [batch, heads, seq, head_dim] GLOBAL arrays sharded on
the seq axis; ring_attention returns the same-sharded output."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.parallel.mesh import SEQ_AXIS


def _ring_attention_local(q, k, v, axis_name, causal, scale):
    """Runs per-device under shard_map. q,k,v: [B,H,Tl,D] local blocks."""
    n = lax.axis_size(axis_name)
    my_rank = lax.axis_index(axis_name)
    b, h, tl, d = q.shape
    q_pos = my_rank * tl + jnp.arange(tl)          # global query positions

    def body(i, carry):
        m, l, o, kb, vb = carry
        # the block we currently hold started at rank (my_rank - i) mod n
        src = jnp.mod(my_rank - i, n)
        k_pos = src * tl + jnp.arange(tl)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, kb) * scale
        if causal:
            mask = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(mask[None, None], s, -jnp.inf)
        blk_max = jnp.max(s, axis=-1)                       # [B,H,Tl]
        new_m = jnp.maximum(m, blk_max)
        # guard fully-masked rows (blk_max = -inf)
        new_m_safe = jnp.where(jnp.isfinite(new_m), new_m, 0.0)
        p = jnp.exp(s - new_m_safe[..., None])
        p = jnp.where(jnp.isfinite(s), p, 0.0)
        correction = jnp.exp(
            jnp.where(jnp.isfinite(m), m - new_m_safe, -jnp.inf))
        correction = jnp.where(jnp.isfinite(m), correction, 0.0)
        new_l = l * correction + jnp.sum(p, axis=-1)
        new_o = o * correction[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, vb)
        # rotate K/V one step around the ring
        perm = [(j, (j + 1) % n) for j in range(n)]
        kb = lax.ppermute(kb, axis_name, perm)
        vb = lax.ppermute(vb, axis_name, perm)
        return new_m, new_l, new_o, kb, vb

    m0 = jnp.full((b, h, tl), -jnp.inf, q.dtype)
    l0 = jnp.zeros((b, h, tl), q.dtype)
    o0 = jnp.zeros((b, h, tl, d), q.dtype)
    m, l, o, _, _ = lax.fori_loop(0, n, body, (m0, l0, o0, k, v))
    return o / jnp.maximum(l, 1e-30)[..., None]


def ring_attention(q, k, v, mesh: Mesh, causal: bool = False,
                   axis: str = SEQ_AXIS, scaled: bool = True):
    """Sequence-parallel attention. q,k,v: [B,H,T,D] sharded over T."""
    if axis not in mesh.axis_names:
        # degenerate mesh (seq axis size 1): plain attention
        return _dense_attention(q, k, v, causal, scaled)
    scale = 1.0 / math.sqrt(q.shape[-1]) if scaled else 1.0
    spec = P(None, None, axis, None)
    local = functools.partial(_ring_attention_local, axis_name=axis,
                              causal=causal, scale=scale)
    fn = shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                   out_specs=spec, check_vma=False)
    return fn(q, k, v)


def _dense_attention(q, k, v, causal, scaled):
    scale = 1.0 / math.sqrt(q.shape[-1]) if scaled else 1.0
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        t_q, t_k = s.shape[-2], s.shape[-1]
        mask = jnp.arange(t_q)[:, None] >= jnp.arange(t_k)[None, :]
        s = jnp.where(mask[None, None], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", w, v)
