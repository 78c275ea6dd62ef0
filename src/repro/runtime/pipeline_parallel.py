"""GPipe-style pipeline parallelism via shard_map + ppermute.

The layer stack is split into P contiguous stages along a 'pipe' mesh axis;
microbatches stream through with the classic (M + P - 1)-tick schedule.
Forward is written with lax.scan over ticks + lax.ppermute stage shifts;
the 1F1B-ish backward emerges from jax autodiff (ppermute transposes to the
reverse shift), so ``jax.grad`` of a pipelined loss just works.

This is an optional beyond-paper extension (DESIGN.md §4): the default
dry-run meshes use DP×TP(+pod); PP composes by adding a 'pipe' axis.

Constraints: homogeneous stacked layers [L, ...] with L % P == 0; global
batch % n_micro == 0; the residual-stream shape is constant across layers.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

Params = Dict[str, Any]


def split_stages(stacked_params: Params, n_stages: int) -> Params:
    """[L, ...] leaves -> [P, L/P, ...] (stage-major) for sharding on axis 0."""
    def resh(x):
        L = x.shape[0]
        assert L % n_stages == 0, (L, n_stages)
        return x.reshape(n_stages, L // n_stages, *x.shape[1:])
    return jax.tree_util.tree_map(resh, stacked_params)


def pipeline_apply(layer_fn: Callable, stage_params: Params, x: jnp.ndarray,
                   n_micro: int, mesh: Mesh, axis: str = "pipe") -> jnp.ndarray:
    """Run x [B, ...] through the pipelined layer stack.

    ``layer_fn(layer_params, x_micro) -> x_micro`` applies ONE layer;
    ``stage_params`` leaves are [P, L/P, ...] (see split_stages).
    Returns the full-batch output, replicated over the pipe axis.
    """
    n_stages = mesh.shape[axis]
    B = x.shape[0]
    assert B % n_micro == 0, (B, n_micro)
    mb = B // n_micro
    micro = x.reshape(n_micro, mb, *x.shape[1:])
    fwd_perm = [(i, i + 1) for i in range(n_stages - 1)]

    def stage_apply(params_local, xm):
        def body(x, lp):
            return layer_fn(lp, x), None
        out, _ = jax.lax.scan(body, xm, params_local)
        return out

    def local_fn(params_stage, micro_in):
        # params_stage: [1, L/P, ...] (this device's stage), micro_in: [M, mb, ...]
        params_local = jax.tree_util.tree_map(lambda t: t[0], params_stage)
        stage = jax.lax.axis_index(axis)
        n_ticks = n_micro + n_stages - 1
        zero = jnp.zeros_like(micro_in[0])
        outputs0 = jnp.zeros_like(micro_in)

        def tick(carry, t):
            recv, outputs = carry
            mi = jnp.clip(t, 0, n_micro - 1)
            inject = jnp.where(stage == 0,
                               jnp.asarray(True), jnp.asarray(False))
            inp = jnp.where(inject, micro_in[mi], recv)
            out = stage_apply(params_local, inp)
            # collect finished microbatch at the last stage
            oi = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            take = (stage == n_stages - 1) & (t >= n_stages - 1)
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs, jnp.where(take, out, outputs[oi]), oi, axis=0)
            recv_next = (jax.lax.ppermute(out, axis, fwd_perm)
                         if n_stages > 1 else out)
            return (recv_next, outputs), None

        (_, outputs), _ = jax.lax.scan(tick, (zero, outputs0),
                                       jnp.arange(n_ticks))
        # only the last stage holds real outputs; broadcast via psum
        outputs = jnp.where(stage == n_stages - 1, outputs, 0.0)
        return jax.lax.psum(outputs, axis)

    from repro.sharding.api import shard_map_unchecked

    in_specs = (P(axis), P())       # stage params sharded; input replicated
    out = shard_map_unchecked(local_fn, mesh, in_specs,
                              P())(stage_params, micro)
    return out.reshape(B, *x.shape[1:])
