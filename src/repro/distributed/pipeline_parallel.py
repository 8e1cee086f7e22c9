"""Pipeline parallelism (GPipe-style microbatching over a 'pipe' mesh axis)
via shard_map + ppermute.

The production mesh assignment for this paper's dry-run is DP x TP (x pod),
but 1000+-node deployments of the deepest assigned archs (granite-34b 88L)
would add a pipe axis; this module provides the schedule and is exercised by
tests on a host-device mesh.

Implementation: layers are split into n_stages contiguous chunks; shard_map
over the 'pipe' axis gives each stage its chunk; the classic GPipe loop runs
n_micro + n_stages - 1 ticks, shifting activations stage-to-stage with
lax.ppermute. Steady-state bubble fraction = (n_stages-1)/(n_micro+n_stages-1).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_apply(fn_stage: Callable, params_stacked, x_micro, *,
                   mesh, n_stages: int, axis: str = "pipe"):
    """Run x through n_stages of fn_stage with GPipe microbatching.

    fn_stage: (stage_params, x) -> x          (one stage's computation)
    params_stacked: pytree with leading dim n_stages (stage-major)
    x_micro: (n_micro, micro_batch, ...) microbatched input
    Returns (n_micro, micro_batch, ...) output (from the LAST stage).
    """
    n_micro = x_micro.shape[0]

    def per_stage(stage_params, xs):
        stage_params = jax.tree_util.tree_map(lambda a: a[0], stage_params)
        stage = jax.lax.axis_index(axis)
        n_ticks = n_micro + n_stages - 1
        buf = jnp.zeros_like(xs)                 # output slots
        carry = jnp.zeros_like(xs[0])            # activation in flight

        def tick(t, state):
            buf, carry = state
            # stage 0 ingests microbatch t (if any); others use carry
            mb = jnp.clip(t, 0, n_micro - 1)
            x_in = jnp.where(stage == 0, xs[mb], carry)
            y = fn_stage(stage_params, x_in)
            # valid iff this stage is processing a real microbatch:
            # stage s processes microbatch (t - s) at tick t
            my_mb = t - stage
            valid = (my_mb >= 0) & (my_mb < n_micro)
            y = jnp.where(valid, y, jnp.zeros_like(y))
            # last stage records its outputs
            slot = jnp.clip(my_mb, 0, n_micro - 1)
            record = valid & (stage == n_stages - 1)
            buf = jnp.where(record,
                            buf.at[slot].set(y), buf)
            # shift activations to the next stage
            carry = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return (buf, carry)

        buf, _ = jax.lax.fori_loop(0, n_ticks, tick, (buf, carry))
        return buf

    per = jax.shard_map(per_stage, mesh=mesh, in_specs=(P(axis), P()),
                        out_specs=P(axis), check_vma=False)
    # every stage gets the full microbatch stream; outputs valid on last stage
    out = per(params_stacked, x_micro)
    # out is stacked over stages along the leading dim; take the last stage
    return out.reshape((n_stages, n_micro) + x_micro.shape[1:])[-1]


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)
