"""Mixture-of-experts routing and dispatch.

One router (`route`) and one set of per-layer loss terms (`router_losses`)
serve both entry points:

- `moe_layer`: the single-program dispatch, dropless. The T x k (token,
  slot) pairs are sorted by expert, the rows gathered in that order, and the
  experts' SwiGLU runs as three grouped matmuls over the ragged groups
  (`ops/grouped_matmul.py`). The k combine weights are brought to sorted
  order too and applied BEFORE the down projection (it is linear), so the
  inverse permutation only brings the k results of a token back together
  for a plain sum: the backward pass needs none of the down projection's
  output, and under remat reruns neither it nor the un-permute. No
  capacity, no dropped token, no [T, E, C] tensor.
- `moe_shard_map`: experts sharded over the `ep` mesh axis, token buffers
  exchanged with `lax.all_to_all`. The exchange needs a static buffer, so
  this path alone is capacity-bounded ([T, E, C] dispatch and combine
  tensors; pairs past an expert's capacity are dropped).
"""

from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from ray_tpu._private import device_profiler
from ray_tpu.ops.grouped_matmul import grouped_matmul


class MoEAux(NamedTuple):
    """What a layer's dispatch hands the losses (and the tests)."""
    experts: jax.Array       # [T, k] int32 the chosen experts
    load_balance: jax.Array  # scalar, `router_losses`
    router_z: jax.Array      # scalar, `router_losses`


class Routing(NamedTuple):
    logits: jax.Array   # [T, E] float32 router logits
    probs: jax.Array    # [T, E] float32 softmax over all experts
    weights: jax.Array  # [T, k] float32 combine weights of the chosen k
    experts: jax.Array  # [T, k] int32 the chosen experts, best first


def route(x, router_w, k: int, norm_topk_prob: bool = False) -> Routing:
    """x [T, D], router_w [D, E] -> the top-k choice per token. Logits,
    softmax and weights are float32 whatever the model dtype: a bf16 logit
    would flip choices between near-equal experts. `norm_topk_prob` divides
    the k weights by their sum (Mixtral; OLMoE leaves them as they are)."""
    with jax.named_scope("moe.route"):
        logits = jnp.dot(x, router_w, precision=jax.lax.Precision.HIGHEST,
                         preferred_element_type=jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        weights, experts = jax.lax.top_k(probs, k)
        if norm_topk_prob:
            weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        return Routing(logits, probs, weights, experts.astype(jnp.int32))


def router_losses(routing: Routing, axis_name=None):
    """-> (load_balance, router_z), one layer's terms, both float32 scalars.

    load_balance = E * sum_i f_i P_i with f_i the share of the T x k
    (token, slot) pairs sent to expert i (a count: no gradient) and P_i the
    mean router probability of expert i; 1 when routing is even. router_z =
    mean_t logsumexp_i(logits)^2. With `axis_name` (inside a shard_map over
    tokens) the statistics are taken over every shard's tokens."""
    t, e = routing.probs.shape
    counts = jnp.bincount(routing.experts.reshape(-1),
                          length=e).astype(jnp.float32)
    p_mean = jnp.mean(routing.probs, axis=0)
    z = jnp.mean(jax.nn.logsumexp(routing.logits, axis=-1) ** 2)
    if axis_name is not None:
        counts = jax.lax.pmean(counts, axis_name)
        p_mean = jax.lax.pmean(p_mean, axis_name)
        z = jax.lax.pmean(z, axis_name)
    f = jax.lax.stop_gradient(counts) / (t * routing.experts.shape[1])
    return e * jnp.sum(f * p_mean), z


def sort_by_expert(experts, n_experts: int):
    """experts [T, k] -> (order, inverse, group_sizes). Pair p = t * k + j is
    token t's j-th choice. `order[s]` is the pair at sorted position s
    (stable: within an expert, pairs keep their order), `inverse[p]` the
    sorted position of pair p, `group_sizes[i]` the number of pairs sent to
    expert i; they sum to T x k, so nothing is dropped."""
    flat = experts.reshape(-1)
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    inverse = jnp.argsort(order).astype(jnp.int32)
    group_sizes = jnp.bincount(flat, length=n_experts).astype(jnp.int32)
    return order, inverse, group_sizes


# The three functions below move rows (or scalars) by a permutation of the
# T x k pairs, so the transpose of each is such a move too: `_permute` and
# `_combine` are each other's, `_reorder`'s is itself under the inverse
# permutation. Autodiff of a gather would emit a scatter-add, which knows
# nothing of that and serialises on the TPU.

@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _permute(x, order, inverse, k):
    """x [T, D] -> rows [T * k, D] in sorted order: row s is the token of
    pair `order[s]`."""
    return x[order // k]


def _permute_fwd(x, order, inverse, k):
    return _permute(x, order, inverse, k), (order, inverse)


def _permute_bwd(k, res, g):
    return _combine(g, *res, k), None, None


_permute.defvjp(_permute_fwd, _permute_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _combine(rows, order, inverse, k):
    """rows [T * k, D] in sorted order -> [T, D]: the sum of each token's k
    rows, accumulated in float32, in rows.dtype. No operand but the indices
    is needed to transpose it."""
    t = inverse.shape[0] // k
    per_pair = rows[inverse].reshape(t, k, -1)
    return jnp.sum(per_pair.astype(jnp.float32), axis=1).astype(rows.dtype)


def _combine_fwd(rows, order, inverse, k):
    return _combine(rows, order, inverse, k), (order, inverse)


def _combine_bwd(k, res, g):
    return _permute(g, *res, k), None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


@jax.custom_vjp
def _reorder(v, index, index_inverse):
    """v [N] -> v[index] for a permutation `index` of N scalars, as a sort
    by the inverse permutation: a gather moves scalars one index at a time
    (0.58 ms for 65,536 on the v5e), the sort takes 0.20 ms (PERF.md §6,
    PR 28)."""
    return jax.lax.sort_key_val(index_inverse, v)[1]


def _reorder_fwd(v, index, index_inverse):
    return _reorder(v, index, index_inverse), (index, index_inverse)


def _reorder_bwd(res, g):
    index, index_inverse = res
    return _reorder(g, index_inverse, index), None, None


_reorder.defvjp(_reorder_fwd, _reorder_bwd)


def moe_layer(x, router_w, experts, k: int, norm_topk_prob: bool = False):
    """Dropless top-k SwiGLU experts. x [T, D]; router_w [D, E]; `experts`
    holds w_gate, w_up [E, D, F] and w_down [E, F, D]. -> (y [T, D] in
    x.dtype, MoEAux): y_t = sum_j w_tj * down_e(silu(gate_e x_t) * up_e x_t)
    over token t's k experts e, accumulated in float32.

    The down projection is linear, so w_tj multiplies its INPUT, on the
    sorted side: silu(gate) * up * w is formed in float32 and rounded to
    x.dtype once. What is left of the combine is then a gather and a sum
    over k with nothing but indices to keep for its transpose. Were the
    weights applied after the down projection, their gradient would need its
    output, and a backward pass that keeps no Pallas call's result (remat
    "dots") would rerun the down matmul and the un-permute for it alone."""
    t = x.shape[0]
    e = router_w.shape[1]
    routing = route(x, router_w, k, norm_topk_prob)
    with jax.named_scope("moe.permute"):
        order, inverse, group_sizes = sort_by_expert(routing.experts, e)
        rows = _permute(x, order, inverse, k)
        w_sorted = _reorder(routing.weights.reshape(-1), order, inverse)
    with jax.named_scope("moe.experts"):
        gate = grouped_matmul(rows, experts["w_gate"], group_sizes)
        up = grouped_matmul(rows, experts["w_up"], group_sizes)
        h = (jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32)
             * w_sorted[:, None]).astype(x.dtype)
        out = grouped_matmul(h, experts["w_down"], group_sizes)
    with jax.named_scope("moe.combine"):
        y = _combine(out, order, inverse, k)
    # per lowering, as `flash.steps_*` are
    device_profiler.count("moe.rows_routed", t * k)
    device_profiler.count("moe.experts", e)
    device_profiler.count("moe.gmm_calls", 3)
    return y, MoEAux(routing.experts, *router_losses(routing))


def _capacity_dispatch(routing: Routing, capacity: int):
    """-> (dispatch, combine), both float32 [T, E, C], for the `ep`
    exchange's static buffers. Slots are assigned in priority order (all
    first choices, then all second choices, ...) with a running per-expert
    offset, so two tokens never share a capacity slot; a pair whose
    position is past `capacity` is dropped."""
    t, e = routing.probs.shape
    dispatch = jnp.zeros((t, e, capacity), dtype=jnp.float32)
    combine = jnp.zeros((t, e, capacity), dtype=jnp.float32)
    expert_counts = jnp.zeros((e,), dtype=jnp.float32)
    for slot in range(routing.experts.shape[1]):
        onehot = jax.nn.one_hot(routing.experts[:, slot], e)     # [T, E]
        pos = (jnp.cumsum(onehot, axis=0) - onehot + expert_counts) * onehot
        pos_in_expert = jnp.sum(pos, axis=-1).astype(jnp.int32)  # [T]
        expert_counts = expert_counts + jnp.sum(onehot, axis=0)
        keep = pos_in_expert < capacity
        cap_onehot = jax.nn.one_hot(pos_in_expert, capacity)     # [T, C]
        d = onehot[:, :, None] * cap_onehot[:, None, :] * keep[:, None, None]
        dispatch = dispatch + d
        combine = combine + d * routing.weights[:, slot][:, None, None]
    return dispatch, combine


def moe_shard_map(x, router_w, expert_fn: Callable, expert_params, mesh,
                  axis_name: str = "ep", k: int = 2,
                  capacity_factor: float = 1.25,
                  norm_topk_prob: bool = False):
    """Expert-parallel variant: tokens and experts sharded over `axis_name`,
    capacity-bounded buffers exchanged with `lax.all_to_all`.
    `expert_fn(params_of_one_expert, rows [C', D]) -> [C', D]`; the leaves
    of `expert_params` lead with the expert dim. -> (y [T, D], MoEAux) with
    the loss terms taken over every shard's tokens (`router_losses`)."""
    from jax.sharding import PartitionSpec as P

    n_exp_total = router_w.shape[1]

    def local_fn(x_loc, router_w_full, params_loc):
        t, d = x_loc.shape
        n_shards = jax.lax.psum(1, axis_name)
        capacity = max(1, int(capacity_factor * t * max(k, 1) / n_exp_total))
        routing = route(x_loc, router_w_full, k, norm_topk_prob)
        dispatch, combine = _capacity_dispatch(routing, capacity)
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
        return y.astype(x_loc.dtype), MoEAux(
            routing.experts, *router_losses(routing, axis_name))

    pspec = jax.tree.map(lambda _: P(axis_name), expert_params)
    return jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(axis_name), P(), pspec),
        out_specs=(P(axis_name), MoEAux(P(axis_name), P(), P())),
        check_vma=False,
    )(x, router_w, expert_params)
