"""Ring attention — sequence/context parallelism over a mesh axis.

The long-context first-class citizen: sequences sharded over the 'sp'
mesh axis, K/V shards rotated around the ring with ppermute while each
device accumulates its queries' attention against every shard, merging
partial softmax results exactly via log-sum-exp. Peak memory per device
is O(T/sp), enabling contexts the reference framework (whole-sequence
LoDTensor attention) could never hold.

Built on shard_map so XLA schedules the ppermute DMA over ICI
concurrently with the local flash-attention compute (communication/
compute overlap, the standard ring schedule).
"""
import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..ops.pallas_attention import attention_with_lse

__all__ = ["ring_attention", "ring_attention_sharded"]


def _merge(o1, lse1, o2, lse2):
    """Exactly combines two partial attention results with their lse."""
    m = jnp.maximum(lse1, lse2)
    w1 = jnp.exp(lse1 - m)[..., None]
    w2 = jnp.exp(lse2 - m)[..., None]
    o = (o1.astype(jnp.float32) * w1 + o2.astype(jnp.float32) * w2) / (w1 + w2)
    lse = m + jnp.log(jnp.exp(lse1 - m) + jnp.exp(lse2 - m))
    return o.astype(o1.dtype), lse


def ring_attention(q, k, v, axis_name, causal=True, scale=None):
    """Per-device body (inside shard_map): q,k,v [B, H, Tlocal, D] shards.

    Device i holds sequence chunk i. At ring step s it attends its queries
    against the K/V chunk that started on device (i - s) mod n, with the
    causal mask applied at chunk granularity via global position offsets.
    """
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    scale = scale or (1.0 / np.sqrt(q.shape[-1]))
    t_local = q.shape[2]

    def step(carry, s):
        k_cur, v_cur, o_acc, lse_acc = carry
        src_chunk = (idx - s) % n  # whose chunk we currently hold
        q_off = idx * t_local
        k_off = src_chunk * t_local
        if causal:
            # bias masks keys whose global pos > query global pos
            rows = q_off + lax.broadcasted_iota(jnp.int32,
                                                (t_local, t_local), 0)
            cols = k_off + lax.broadcasted_iota(jnp.int32,
                                                (t_local, t_local), 1)
            bias = jnp.where(rows >= cols, 0.0, -1e30)
        else:
            bias = None
        o_part, lse_part = attention_with_lse_biased(q, k_cur, v_cur, scale,
                                                     bias)
        o_new, lse_new = _merge(o_acc, lse_acc, o_part, lse_part)
        # rotate k/v one step around the ring
        perm = [(j, (j + 1) % n) for j in range(n)]
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return (k_nxt, v_nxt, o_new, lse_new), None

    o0 = jnp.zeros_like(q)
    lse0 = jnp.full(q.shape[:3], -1e30, jnp.float32)
    (_, _, o, _), _ = lax.scan(step, (k, v, o0, lse0), jnp.arange(n))
    return o


def attention_with_lse_biased(q, k, v, scale, bias):
    from ..ops.pallas_attention import _ref_attention_lse
    return _ref_attention_lse(q, k, v, scale, causal=False, bias=bias)


def ring_attention_sharded(q, k, v, mesh, axis="sp", causal=True,
                           scale=None):
    """Global entry: q,k,v [B, H, T, D] with T sharded over ``axis``."""
    spec = P(None, None, axis, None)
    fn = jax.shard_map(
        functools.partial(ring_attention, axis_name=axis, causal=causal,
                          scale=scale),
        mesh=mesh.mesh if hasattr(mesh, "mesh") else mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False)
    return fn(q, k, v)
