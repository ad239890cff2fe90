"""Pipeline parallelism: GPipe-style microbatch schedule inside one jit.

The compiled-graph/aDAG capability of the reference (ray:
python/ray/dag/compiled_dag_node.py — static actor pipelines over
mutable-object channels with NCCL sends) re-designed the TPU way: stages are
shards of a `pp` mesh axis, microbatch activations move between stages with
`jax.lax.ppermute` (ICI collective-permute), and the whole schedule is a
`lax.scan` the XLA scheduler can overlap. No channels, no actors in the inner
loop — the pipeline IS the program.

Layout convention: layer parameters are stacked on a leading `stage` axis of
size pp (each stage holds its own slice); inputs arrive as [num_microbatches,
microbatch, ...] sharded so every stage sees all microbatches.
"""

from __future__ import annotations

import functools
from typing import Any, Callable


def pipeline_apply(
    stage_fn: Callable,           # (stage_params, x) -> y, one stage's compute
    stage_params: Any,            # pytree; leaves lead with the local stage dim
    microbatches,                 # [M, mb, ...] identical on every stage
    axis_name: str = "pp",
):
    """Run the GPipe schedule; returns [M, mb, ...] final-stage outputs
    (valid on every device — the result is broadcast back around the ring)."""
    import jax
    import jax.numpy as jnp

    n_stages = jax.lax.psum(1, axis_name)
    stage_id = jax.lax.axis_index(axis_name)
    m = microbatches.shape[0]
    total_steps = m + n_stages - 1
    perm_fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    mb_shape = microbatches.shape[1:]

    def step(carry, t):
        buf, outputs = carry
        # Stage 0 injects microbatch t (when valid); others take the buffer
        # that arrived from the left neighbor last step.
        mb_index = jnp.clip(t, 0, m - 1)
        inject = microbatches[mb_index]
        x = jnp.where(stage_id == 0, inject, buf)
        y = stage_fn(stage_params, x)
        # The last stage's output for microbatch (t - n_stages + 1) is ready.
        out_index = t - n_stages + 1
        valid = (out_index >= 0) & (out_index < m)
        outputs = jax.lax.cond(
            valid,
            lambda o: o.at[jnp.clip(out_index, 0, m - 1)].set(
                jnp.where(stage_id == n_stages - 1, y, o[jnp.clip(out_index, 0, m - 1)])
            ),
            lambda o: o,
            outputs,
        )
        buf_next = jax.lax.ppermute(y, axis_name, perm_fwd)
        return (buf_next, outputs), None

    buf0 = jnp.zeros(mb_shape, dtype=microbatches.dtype)
    outputs0 = jnp.zeros((m,) + mb_shape, dtype=microbatches.dtype)
    (_, outputs), _ = jax.lax.scan(
        step, (buf0, outputs0), jnp.arange(total_steps)
    )
    # Only the last stage holds real outputs; broadcast them to all stages so
    # downstream (loss) code is SPMD-uniform. psum of masked outputs = select.
    mask = (stage_id == n_stages - 1).astype(outputs.dtype)
    outputs = jax.lax.psum(outputs * mask, axis_name)
    return outputs


def pipeline_sharded(stage_fn, mesh, axis_name: str = "pp"):
    """shard_map wrapper: params lead with a [pp, ...] stage axis, inputs are
    replicated microbatches; returns final outputs replicated."""
    import jax
    from jax.sharding import PartitionSpec as P

    def wrapped(stacked_params, microbatches):
        fn = functools.partial(pipeline_apply, stage_fn, axis_name=axis_name)
        param_specs = jax.tree.map(lambda _: P(axis_name), stacked_params)
        return jax.shard_map(
            fn, mesh=mesh,
            in_specs=(param_specs, P()),
            out_specs=P(),
            check_vma=False,
        )(stacked_params, microbatches)

    return wrapped
