"""Pipeline parallelism over the pod axis (gpipe-style, beyond-paper).

On the multi-pod mesh the ``pod`` axis defaults to extra data parallelism;
with cross-pod links an order of magnitude thinner than in-pod ICI, pipeline
parallelism is the other sensible use: pod p owns layers [p·L/P, (p+1)·L/P),
microbatches flow pod→pod via ``collective_permute`` (one activation tensor
per boundary per microbatch — the minimum possible cross-pod traffic).

``pipelined_forward`` is the inference/eval path (training composes with
jax.grad through shard_map; the trainer keeps DP as its default because at
2 pods the bubble is 1/(1+2(M...)) — PP pays off at 4+ pods / thin links,
which is exactly when this module's traffic profile wins).

Schedule (gpipe, P stages, M microbatches, T = M + P - 1 ticks):
  tick t: stage p processes microbatch (t - p) if 0 <= t - p < M.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


PyTree = Any


def pipelined_forward(layer_fn: Callable, params_stacked: PyTree,
                      x: jnp.ndarray, *, mesh, num_microbatches: int,
                      axis: str = "pod") -> jnp.ndarray:
    """Run ``layer_fn(params_slice, x) -> x`` over pipeline stages.

    params_stacked: leaves [num_layers, ...] — layers are split evenly over
    the ``axis`` mesh dimension (stage-local leading dim = layers/P).
    x: [B, ...] global batch — microbatched along dim 0.
    """
    n_stages = mesh.shape[axis]
    b = x.shape[0]
    assert b % num_microbatches == 0, (b, num_microbatches)
    mb = b // num_microbatches

    def stage_body(params_local, x_local):
        """Runs on ONE pod: its slice of layers over one microbatch."""
        def one(x_mb):
            def body(h, p_slice):
                return layer_fn(p_slice, h), None
            h, _ = jax.lax.scan(body, x_mb, params_local)
            return h
        return one(x_local)

    def pipeline(params_local, x_all):
        stage = jax.lax.axis_index(axis)
        ticks = num_microbatches + n_stages - 1
        # buffer of microbatches [M, mb, ...]; stage 0 feeds from it
        mbs = x_all.reshape(num_microbatches, mb, *x_all.shape[1:])
        cur = jnp.zeros_like(mbs[0])
        outs = jnp.zeros_like(mbs)

        def tick(carry, t):
            cur, outs = carry
            feed_idx = jnp.clip(t, 0, num_microbatches - 1)
            inject = jnp.where(stage == 0,
                               jnp.asarray(1, jnp.int32),
                               jnp.asarray(0, jnp.int32))
            cur = jnp.where((stage == 0) & (t < num_microbatches),
                            mbs[feed_idx], cur)
            active = (t - stage >= 0) & (t - stage < num_microbatches)
            y = stage_body(params_local, cur)
            y = jnp.where(active, y, cur)
            # last stage banks its result
            done_idx = jnp.clip(t - stage, 0, num_microbatches - 1)
            outs = jnp.where(
                (stage == n_stages - 1) & active,
                outs.at[done_idx].set(y), outs)
            # pass activations to the next stage
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            nxt = jax.lax.ppermute(y, axis, perm)
            return (nxt, outs), None

        (_, outs), _ = jax.lax.scan(tick, (cur, outs), jnp.arange(ticks))
        # only the last stage holds real outputs; broadcast them
        outs = jax.lax.psum(
            jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs)),
            axis)
        return outs.reshape(b, *x_all.shape[1:])

    other_axes = tuple(a for a in mesh.axis_names if a != axis)
    fn = jax.shard_map(
        pipeline, mesh=mesh,
        in_specs=(P(axis), P()),  # layers over pods; batch replicated
        out_specs=P(),
        axis_names={axis}, check_vma=False)
    return fn(params_stacked, x)


def reference_forward(layer_fn: Callable, params_stacked: PyTree,
                      x: jnp.ndarray) -> jnp.ndarray:
    """Oracle: plain sequential scan over all layers."""
    def body(h, p_slice):
        return layer_fn(p_slice, h), None
    h, _ = jax.lax.scan(body, x, params_stacked)
    return h
