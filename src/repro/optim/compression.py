"""Error-feedback int8 gradient compression for the cross-pod (DCN) axis.

The pod axis of the production mesh crosses the datacenter fabric the paper
models (MRLS).  Even with the MRLS All2All advantage, DP gradient sync
across pods is bandwidth-precious, so the framework offers EF-int8: each
step sends int8-quantized gradients (4x fewer bytes than f32, 2x fewer than
bf16) and carries the quantization error forward (error feedback keeps the
method unbiased over time — Karimireddy et al., 2019).

``compress`` / ``decompress`` are pure and jit-safe; ``compressed_psum``
shows the shard_map pattern for applying them around a pod-axis psum.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def compress(g, ef):
    """g: f32/bf16 tensor; ef: error-feedback buffer (same shape, f32).
    Returns (q int8, scale f32 scalar, new_ef)."""
    gf = g.astype(jnp.float32) + ef
    scale = jnp.maximum(jnp.max(jnp.abs(gf)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(gf / scale), -127, 127).astype(jnp.int8)
    new_ef = gf - q.astype(jnp.float32) * scale
    return q, scale, new_ef


def decompress(q, scale, dtype=jnp.float32):
    return (q.astype(jnp.float32) * scale).astype(dtype)


def compress_tree(grads, ef_tree):
    flat_g, tdef = jax.tree.flatten(grads)
    flat_e = jax.tree.leaves(ef_tree)
    qs, scales, efs = [], [], []
    for g, e in zip(flat_g, flat_e):
        q, s, ne = compress(g, e)
        qs.append(q); scales.append(s); efs.append(ne)
    return (jax.tree.unflatten(tdef, qs),
            jax.tree.unflatten(tdef, scales),
            jax.tree.unflatten(tdef, efs))


def decompress_tree(qs, scales, like):
    return jax.tree.map(
        lambda q, s, l: decompress(q, s, l.dtype), qs, scales, like)


def compressed_psum(x, ef, mesh, axis: str = "pod"):
    """EF-int8 all-reduce over ``axis``: quantize locally, all-gather int8
    (the wire format), sum in f32.  Bytes on the DCN: 1 per element instead
    of 4."""
    def inner(xl, el):
        q, s, ne = compress(xl, el)
        qg = jax.lax.all_gather(q, axis)                 # int8 on the wire
        sg = jax.lax.all_gather(s, axis)
        total = jnp.tensordot(sg, qg.astype(jnp.float32), axes=((0,), (0,)))
        return total.astype(xl.dtype), ne

    return jax.shard_map(
        inner, mesh=mesh,
        in_specs=(P(), P()), out_specs=(P(), P()),
        check_vma=False,
    )(x, ef)
