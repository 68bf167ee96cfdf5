"""Cross-pod gradient compression + two-level reduction.

The paper's core constraint is the *slow link between the two
environments* (cluster <-> cloud Ethernet; here: inter-pod DCI vs
intra-pod ICI).  Baseline SPMD lets XLA all-reduce gradients over
(pod, data) jointly — every gradient byte crosses DCI at fp32/bf16 width.
This module implements the beyond-paper optimization: gradients are
reduced over "data" (fast ICI) by XLA automatically, then exchanged
across the "pod" axis explicitly in int8 (blockwise absmax), cutting DCI
bytes 4x vs fp32 / 2x vs bf16.

Mechanically this relies on shard_map's `auto` axes: the train step runs
manual over "pod" only (each pod is a paper "environment"), automatic
over data/model, and calls `cross_pod_reduce` on the per-pod gradient
pytree.  For 2 pods the exchange is a single ppermute of int8 payloads +
local dequant-add, which keeps the wire format actually 8-bit (a psum of
dequantized values would not).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


CBLOCK = 256


def _q8(x: jax.Array):
    """Blockwise int8 quantization (flattened blocks of CBLOCK)."""
    flat = x.reshape(-1)
    n = flat.shape[0]
    pad = (-n) % CBLOCK
    if pad:
        flat = jnp.pad(flat, (0, pad))
    blocks = flat.reshape(-1, CBLOCK)
    scale = jnp.max(jnp.abs(blocks), axis=-1, keepdims=True) / 127.0
    q = jnp.round(blocks / jnp.maximum(scale, 1e-20)).astype(jnp.int8)
    return q, scale, n


def _dq8(q: jax.Array, scale: jax.Array, n: int, shape) -> jax.Array:
    flat = (q.astype(jnp.float32) * scale).reshape(-1)[:n]
    return flat.reshape(shape)


def cross_pod_reduce(grads, axis: str = "pod", method: str = "int8"):
    """All-reduce a gradient pytree over `axis` (manual shard_map axis).

    method="none": plain psum (baseline).
    method="int8": quantize -> exchange int8 via ppermute ring -> local
    dequant-add.  Exact for 2 pods; for P pods it performs P-1 ring hops
    (each hop re-quantizes its own share — bounded error, documented).
    """
    if method == "none":
        return jax.tree.map(lambda g: jax.lax.psum(g, axis), grads)

    def reduce_leaf(g):
        npods = jax.lax.axis_size(axis)
        acc = g.astype(jnp.float32)
        q, scale, n = _q8(g.astype(jnp.float32))
        for hop in range(1, npods):
            perm = [(i, (i + hop) % npods) for i in range(npods)]
            q_r = jax.lax.ppermute(q, axis, perm)
            s_r = jax.lax.ppermute(scale, axis, perm)
            acc = acc + _dq8(q_r, s_r, n, g.shape)
        return acc.astype(g.dtype)

    return jax.tree.map(reduce_leaf, grads)


def compressed_bytes(n_params: int) -> tuple[int, int]:
    """(wire bytes with int8, wire bytes with fp32) per pod-hop."""
    blocks = (n_params + CBLOCK - 1) // CBLOCK
    return n_params + 4 * blocks, 4 * n_params
