"""Gradient compression utilities.

Two levels (DESIGN.md §4):

1. ``ef_encode``/``ef_decode`` — error-feedback int8 block quantization of a
   gradient tree.  Used by train_step's microbatch accumulator; the
   quantization residual is carried into the next microbatch so the bias
   vanishes over steps (Seide et al. / EF-SGD).

2. ``ring_allreduce_q8`` — a shard_map ring all-reduce whose wire format is
   int8 (+ one f32 scale per chunk): reduce-scatter then all-gather, both
   phases moving int8 payloads via collective_permute.  On a real fleet this
   is the DCN-crossing (pod-axis) gradient sync at ~1/4 wire bytes; the s8
   collective-permutes are visible in lowered HLO, which is how the roofline
   collective term credits it.  Tested on a subprocess CPU mesh.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
_BLOCK = 256


class EFQ(NamedTuple):
    q: jnp.ndarray        # int8 blocks [n, _BLOCK]
    scale: jnp.ndarray    # f32 [n, 1]
    shape: tuple = ()
    size: int = 0


def ef_encode(x: jnp.ndarray) -> EFQ:
    flat = x.astype(F32).reshape(-1)
    pad = (-flat.size) % _BLOCK
    blocks = jnp.pad(flat, (0, pad)).reshape(-1, _BLOCK)
    s = jnp.max(jnp.abs(blocks), axis=1, keepdims=True) / 127.0
    q = jnp.round(blocks / jnp.maximum(s, 1e-20)).astype(jnp.int8)
    return EFQ(q=q, scale=s, shape=tuple(x.shape), size=x.size)


def ef_decode(t: EFQ) -> jnp.ndarray:
    flat = (t.q.astype(F32) * t.scale).reshape(-1)
    return flat[: t.size].reshape(t.shape)


jax.tree_util.register_pytree_node(
    EFQ,
    lambda t: ((t.q, t.scale), (t.shape, t.size)),
    lambda aux, ch: EFQ(q=ch[0], scale=ch[1], shape=aux[0], size=aux[1]),
)


# ---------------------------------------------------------------------------
# int8-wire ring all-reduce (shard_map collective)
# ---------------------------------------------------------------------------


def _q8(x):
    s = jnp.max(jnp.abs(x)) / 127.0
    q = jnp.round(x / jnp.maximum(s, 1e-20)).astype(jnp.int8)
    return q, s.reshape(1)


def ring_allreduce_q8(x: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """Sum ``x`` across ``axis_name`` with int8 wire format.

    Must be called inside shard_map with ``axis_name`` un-sharded in x
    (i.e. x is the local shard).  Quantization applies to the partial sums
    exchanged between neighbours (ring reduce-scatter, then ring
    all-gather of the final chunks).
    """
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        return x
    size = x.size
    pad = (-size) % n
    flat = jnp.pad(x.astype(F32).reshape(-1), (0, pad)).reshape(n, -1)
    idx = jax.lax.axis_index(axis_name)
    fwd = [(i, (i + 1) % n) for i in range(n)]

    def rs_step(k, acc):
        send_ix = (idx - k) % n
        payload = jax.lax.dynamic_index_in_dim(acc, send_ix, 0, keepdims=False)
        q, s = _q8(payload)
        q_r = jax.lax.ppermute(q, axis_name, fwd)
        s_r = jax.lax.ppermute(s, axis_name, fwd)
        recv_ix = (idx - k - 1) % n
        return jax.lax.dynamic_update_index_in_dim(
            acc, jax.lax.dynamic_index_in_dim(acc, recv_ix, 0, False)
            + q_r.astype(F32) * s_r, recv_ix, 0)

    acc = jax.lax.fori_loop(0, n - 1, rs_step, flat)

    # each rank now owns the fully-reduced chunk (idx + 1) % n
    def ag_step(k, acc):
        send_ix = (idx + 1 - k) % n
        payload = jax.lax.dynamic_index_in_dim(acc, send_ix, 0, keepdims=False)
        q, s = _q8(payload)
        q_r = jax.lax.ppermute(q, axis_name, fwd)
        s_r = jax.lax.ppermute(s, axis_name, fwd)
        recv_ix = (idx - k) % n
        return jax.lax.dynamic_update_index_in_dim(
            acc, q_r.astype(F32) * s_r, recv_ix, 0)

    acc = jax.lax.fori_loop(0, n - 1, ag_step, acc)
    return acc.reshape(-1)[:size].reshape(x.shape).astype(x.dtype)
