"""Mixture-of-Experts dispatch with expert parallelism.

EP capability absent from the reference (SURVEY.md §5): top-k routing with
capacity, dispatch/combine as einsums against an expert-sharded weight stack.
Under pjit, annotating the expert dim with the `ep` mesh axis makes XLA emit
the all-to-alls; `moe_shard_map` offers the explicit `lax.all_to_all` form
for when manual control wins.
"""

from __future__ import annotations

from typing import Callable, Tuple


def top_k_gating(logits, k: int, capacity: int):
    """Compute dispatch/combine tensors for top-k routing with capacity.

    logits: [T, E]. Returns (dispatch [T, E, C] one-hot-ish, combine
    [T, E, C] weights, aux_loss scalar).
    """
    import jax
    import jax.numpy as jnp

    t, e = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)            # [T, k]
    # Load-balancing auxiliary loss (Switch-style).
    me = jnp.mean(probs, axis=0)                             # [E]
    top1 = jax.nn.one_hot(gate_idx[:, 0], e)
    ce = jnp.mean(top1, axis=0)
    aux_loss = e * jnp.sum(me * ce)

    # Position of each token within its expert's buffer. Slots are assigned
    # in priority order (all slot-0 choices first, then slot-1, ...) with a
    # running per-expert offset so a token picking expert E as 1st choice and
    # another picking E as 2nd choice never collide in the same capacity slot.
    dispatch = jnp.zeros((t, e, capacity), dtype=jnp.float32)
    combine = jnp.zeros((t, e, capacity), dtype=jnp.float32)
    expert_counts = jnp.zeros((e,), dtype=jnp.float32)
    for slot in range(k):
        idx = gate_idx[:, slot]                              # [T]
        onehot = jax.nn.one_hot(idx, e)                      # [T, E]
        pos = (jnp.cumsum(onehot, axis=0) - onehot + expert_counts) * onehot
        pos_in_expert = jnp.sum(pos, axis=-1).astype(jnp.int32)  # [T]
        expert_counts = expert_counts + jnp.sum(onehot, axis=0)
        keep = pos_in_expert < capacity
        cap_onehot = jax.nn.one_hot(pos_in_expert, capacity)  # [T, C]
        d = onehot[:, :, None] * cap_onehot[:, None, :] * keep[:, None, None]
        dispatch = dispatch + d
        combine = combine + d * gate_vals[:, slot][:, None, None]
    return dispatch, combine, aux_loss


def moe_layer(x, gate_w, expert_fn: Callable, expert_params,
              k: int = 2, capacity_factor: float = 1.25):
    """Apply an MoE layer. x: [T, D]; gate_w: [D, E]; expert_params leaves
    lead with the expert dim E (annotate it with the `expert` logical axis so
    pjit shards it over `ep`). Returns ([T, D], aux_loss)."""
    import jax.numpy as jnp
    import jax

    t, d = x.shape
    e = gate_w.shape[1]
    capacity = max(1, int(capacity_factor * t * max(k, 1) / e))
    logits = x.astype(jnp.float32) @ gate_w.astype(jnp.float32)
    dispatch, combine, aux = top_k_gating(logits, k, capacity)
    # [E, C, D]: per-expert token buffers.
    expert_in = jnp.einsum("tec,td->ecd", dispatch, x.astype(jnp.float32))
    expert_out = jax.vmap(expert_fn)(expert_params, expert_in.astype(x.dtype))
    out = jnp.einsum("tec,ecd->td", combine, expert_out.astype(jnp.float32))
    return out.astype(x.dtype), aux


def moe_shard_map(x, gate_w, expert_fn, expert_params, mesh,
                  axis_name: str = "ep", k: int = 2,
                  capacity_factor: float = 1.25):
    """Explicit-collective variant: experts sharded over `axis_name`, token
    buffers exchanged with lax.all_to_all."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    n_exp_total = gate_w.shape[1]

    def local_fn(x_loc, gate_w_full, params_loc):
        t, d = x_loc.shape
        n_shards = jax.lax.psum(1, axis_name)
        capacity = max(1, int(capacity_factor * t * max(k, 1) / n_exp_total))
        logits = x_loc.astype(jnp.float32) @ gate_w_full.astype(jnp.float32)
        dispatch, combine, aux = top_k_gating(logits, k, capacity)
        buf = jnp.einsum("tec,td->ecd", dispatch, x_loc.astype(jnp.float32))
        # [E, C, D] -> exchange so each shard holds its experts' tokens from
        # every shard: split E across shards.
        buf = buf.reshape(n_shards, n_exp_total // n_shards, capacity, d)
        buf = jax.lax.all_to_all(buf, axis_name, 0, 0, tiled=False)
        # buf: [n_shards(src), E_local, C, D] -> merge src into capacity dim
        e_loc = n_exp_total // n_shards
        buf = buf.transpose(1, 0, 2, 3).reshape(e_loc, n_shards * capacity, d)
        out = jax.vmap(expert_fn)(params_loc, buf.astype(x_loc.dtype))
        out = out.reshape(e_loc, n_shards, capacity, d).transpose(1, 0, 2, 3)
        out = jax.lax.all_to_all(out, axis_name, 0, 0, tiled=False)
        out = out.reshape(n_exp_total, capacity, d)
        y = jnp.einsum("tec,ecd->td", combine, out.astype(jnp.float32))
        # aux is computed from this shard's tokens only; the result is
        # declared replicated (out_specs=P()), so it must actually BE the
        # global mean, not one shard's local value.
        return y.astype(x_loc.dtype), jax.lax.pmean(aux, axis_name)

    pspec = jax.tree.map(lambda _: P(axis_name), expert_params)
    return jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(axis_name), P(), pspec),
        out_specs=(P(axis_name), P()),
        check_vma=False,
    )(x, gate_w, expert_params)
