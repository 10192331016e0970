"""Pipeline parallelism over the pod axis (GPipe-style, shard_map).

On the multi-pod mesh the `pod` axis crosses DCN; instead of data-parallel
replication across pods, the layer stack can be SPLIT across pods (each pod
holds n_layers / n_stages layers) and microbatches stream through:

  stage s, step t processes microbatch (t - s); activations hop one pod per
  step over collective_permute.  Total steps = n_micro + n_stages - 1;
  bubble fraction = (n_stages - 1) / (n_micro + n_stages - 1).

This module implements the *forward* pipeline as a composable shard_map
program over stacked per-layer parameters (the same stacked pytrees the
model zoo uses).  It is exact: tests/test_distributed.py checks the
pipelined forward equals the sequential scan on a subprocess mesh.

Why GPipe (not 1F1B): with 2 pods the schedule difference is one
microbatch of bubble; the win here is the structure — per-pod weight
residency (half the params per pod) and DCN traffic = one [mb_tokens, d]
activation per step, which is what the multi-pod roofline needs priced.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_forward(layer_fn: Callable, stacked_params, x: jnp.ndarray,
                     *, mesh, axis: str = "pod", n_micro: int = 4):
    """Run x through a layer stack split across ``axis``.

    layer_fn(params_slice, h) -> h          (one layer)
    stacked_params: pytree with leading dim n_layers (divisible by n_stages)
    x: [B, ...] activations (B divisible by n_micro)

    Returns the same result as scanning layer_fn over all layers.
    """
    n_stages = mesh.shape[axis]
    n_layers = jax.tree.leaves(stacked_params)[0].shape[0]
    assert n_layers % n_stages == 0
    per_stage = n_layers // n_stages
    B = x.shape[0]
    assert B % n_micro == 0

    # split layers across the pipeline axis: [n_layers,...] -> [n_stages*...]
    def split(p):
        return p.reshape(n_stages, per_stage, *p.shape[1:])
    staged = jax.tree.map(split, stacked_params)

    p_specs = jax.tree.map(lambda _: P(axis), staged)

    def stage_prog(params_local, xs):
        """Runs on one pod: params_local has leading dims [1, per_stage,...];
        xs [B, ...] (full batch, replicated input)."""
        params_local = jax.tree.map(lambda p: p[0], params_local)
        sid = jax.lax.axis_index(axis)
        mb = xs.reshape(n_micro, B // n_micro, *xs.shape[1:])
        steps = n_micro + n_stages - 1
        fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def run_stage(h):
            def body(hh, pl):
                return layer_fn(pl, hh), None
            out, _ = jax.lax.scan(body, h, params_local)
            return out

        def step(carry, t):
            buf, out = carry                       # buf: incoming activation
            # stage s works on microbatch t - s when 0 <= t-s < n_micro
            m = t - sid
            active = (m >= 0) & (m < n_micro)
            inp = jnp.where(sid == 0,
                            mb[jnp.clip(m, 0, n_micro - 1)], buf)
            res = run_stage(inp)
            res = jnp.where(active, res, jnp.zeros_like(res))
            # last stage banks its finished microbatch
            out = jax.lax.cond(
                active & (sid == n_stages - 1),
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, res, jnp.clip(m, 0, n_micro - 1), 0),
                lambda o: o, out)
            # hop activations to the next stage over the pod link
            buf = jax.lax.ppermute(res, axis, fwd)
            return (buf, out), None

        buf0 = jnp.zeros_like(mb[0])
        out0 = jnp.zeros_like(mb)
        (_, out), _ = jax.lax.scan(step, (buf0, out0), jnp.arange(steps))
        # every pod returns the same banked output (only the last stage
        # filled it) — broadcast via a masked psum.
        out = jax.lax.psum(
            jnp.where(sid == n_stages - 1, out, jnp.zeros_like(out)), axis)
        return out.reshape(B, *xs.shape[1:])

    prog = jax.shard_map(stage_prog, mesh=mesh,
                         in_specs=(p_specs, P()), out_specs=P(),
                         check_vma=False)
    return prog(staged, x)
