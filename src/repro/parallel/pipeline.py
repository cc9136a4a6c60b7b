"""GPipe-style pipeline parallelism over a mesh axis (shard_map + ppermute).

TRINE's stage-count argument applies to pipelines too: each pipeline hop is
one interposer crossing, so the schedule below keeps exactly S-1 nearest-
neighbour hops per microbatch (a `collective_permute` ring over the `pipe`
axis) instead of any all-to-all style exchange — activations cross the slow
boundary once per stage, the minimum the dataflow admits.

Design (GPipe / praxis-style, differentiable through the schedule):

  * the layer stack is split into S contiguous stages; each stage's stacked
    params live on its own pipe-axis slice (shard_map hands each device its
    local slice),
  * the global batch is split into M microbatches; a `lax.scan` over
    M + S - 1 clock ticks drives the classic staircase — stage s works on
    microbatch t - s at tick t,
  * activations hop stage→stage with `jax.lax.ppermute`; `jax.grad`
    differentiates through the schedule (ppermute transposes to the reverse
    permutation), giving the backward staircase automatically,
  * bubble fraction = (S-1)/(M+S-1), reported by `pipeline_cost` and used by
    the planner to pick M (bandwidth matching: enough microbatches that the
    bubble is amortized, no more — "without wasting network resources").

This module is deliberately model-agnostic: `stage_fn(stage_params, x)` is
any per-stage function (tests drive it with both MLP stacks and the repo's
transformer blocks).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P



def split_stages(stacked_params, n_stages: int):
    """(L, ...) stacked layer params -> (S, L/S, ...) stage-stacked."""
    def f(x):
        l = x.shape[0]
        assert l % n_stages == 0, (l, n_stages)
        return x.reshape(n_stages, l // n_stages, *x.shape[1:])
    return jax.tree.map(f, stacked_params)


def pipeline_cost(n_stages: int, n_micro: int, step_flops: float,
                  hop_bytes: float, peak_flops: float, link_bw: float):
    """Napkin model used by tests and the planner: total ticks, bubble
    fraction, and the per-tick compute/communication times."""
    ticks = n_micro + n_stages - 1
    bubble = (n_stages - 1) / ticks
    compute_tick = step_flops / max(n_micro, 1) / peak_flops
    comm_tick = hop_bytes / link_bw
    return {"ticks": ticks, "bubble_frac": bubble,
            "tick_s": max(compute_tick, comm_tick),
            "total_s": ticks * max(compute_tick, comm_tick)}


def choose_microbatches(n_stages: int, target_bubble: float = 0.1,
                        max_micro: int = 64) -> int:
    """Bandwidth matching for the pipe: smallest M with bubble <= target."""
    m = 1
    while (n_stages - 1) / (m + n_stages - 1) > target_bubble and m < max_micro:
        m *= 2
    return m


def pipelined_apply(
    stage_fn: Callable,
    stage_params,           # pytree, leaves (S, ...) — stage dim sharded on `axis`
    x: jax.Array,           # (M, mb, ...) microbatched input
    mesh: Mesh,
    axis: str = "pipe",
):
    """Run x through S pipeline stages; returns (M, mb, ...) outputs (valid
    on every device — the final ppermute broadcasts... no: outputs are
    gathered with a psum-mask so the result is replicated along `axis`).

    Correctness contract (tested): equals applying the S stage_fns
    sequentially on each microbatch.
    """
    n_stages = mesh.shape[axis]
    n_micro = x.shape[0]
    ticks = n_micro + n_stages - 1

    pspec_params = jax.tree.map(lambda _: P(axis), stage_params)
    in_specs = (pspec_params, P())          # params stage-sharded; x replicated
    out_specs = P()

    def run(local_params, xs):
        # local_params leaves: (1, ...) — this device's stage
        local_params = jax.tree.map(lambda a: a[0], local_params)
        stage = jax.lax.axis_index(axis)
        mb_shape = xs.shape[1:]

        def tick(carry, t):
            buf, outs = carry                      # buf: activation entering this stage
            inject = jnp.clip(t, 0, n_micro - 1)
            x_in = jnp.where(stage == 0,
                             xs[inject].astype(buf.dtype), buf)
            h = stage_fn(local_params, x_in)
            # collect at the last stage when its microbatch is valid
            out_idx = t - (n_stages - 1)
            valid = (out_idx >= 0) & (out_idx < n_micro)
            outs = jax.lax.cond(
                valid & (stage == n_stages - 1),
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, h, jnp.clip(out_idx, 0, n_micro - 1), 0),
                lambda o: o, outs)
            # hop to the next stage (ring; the wrap-around value is ignored)
            nbuf = jax.lax.ppermute(
                h, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return (nbuf, outs), None

        buf0 = jnp.zeros(mb_shape, xs.dtype)
        outs0 = jnp.zeros((n_micro,) + mb_shape, xs.dtype)
        (_, outs), _ = jax.lax.scan(tick, (buf0, outs0), jnp.arange(ticks))
        # replicate the result along the pipe axis (only the last stage holds it)
        outs = jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs))
        return jax.lax.psum(outs, axis)

    return jax.shard_map(run, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                     check_vma=False)(stage_params, x)


def sequential_reference(stage_fn: Callable, stage_params, x: jax.Array):
    """Oracle: the same stages applied back-to-back (no pipelining)."""
    n_stages = jax.tree.leaves(stage_params)[0].shape[0]

    def one_micro(xm):
        h = xm
        for s in range(n_stages):
            p_s = jax.tree.map(lambda a: a[s], stage_params)
            h = stage_fn(p_s, h)
        return h

    return jax.vmap(one_micro)(x)
