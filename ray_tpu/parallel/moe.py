"""Mixture-of-experts routing and dispatch.

One router (`route`) and one set of per-layer loss terms (`router_losses`)
serve both entry points:

- `moe_layer`: the single-program dispatch, dropless. The T x k (token,
  slot) pairs are sorted by expert, the rows gathered in that order, and the
  experts' SwiGLU runs as three grouped matmuls over the ragged groups
  (`ops/grouped_matmul.py`; three for the `reglu` form too, two for the
  two-matrix `relu2` form, `_expert_hidden`). The k combine weights are
  brought to sorted order too and applied BEFORE the down projection (it is
  linear), so the inverse permutation only brings the k results of a token
  back together for a plain sum: the backward pass needs none of the down
  projection's output, and under remat reruns neither it nor the
  un-permute. No capacity, no dropped token, no [T, E, C] tensor.
  Told which experts it holds (`held=(first_expert, n_held)`: one chip's
  share of an expert-parallel deployment, run without its exchange), it
  routes over ALL the router's experts and computes only the (token, slot)
  pairs whose expert is held (`_held_experts`): what the absent experts
  would add is left out, nothing that lands here is dropped, and device
  time follows the rows that landed: the grouped matmuls' and the row
  moves' to a tile of rows, the elementwise work to a factor of two.
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
from ray_tpu.ops.row_moves import row_tiles, take_live_rows
from ray_tpu.ops.row_sums import sum_rows_by_token


class MoEAux(NamedTuple):
    """What a layer's dispatch hands the losses (and the tests)."""
    experts: jax.Array       # [T, k] int32 the chosen experts
    load_balance: jax.Array  # scalar, `router_losses`
    router_z: jax.Array      # scalar, `router_losses`


class Routing(NamedTuple):
    logits: jax.Array   # [T, E] float32 router logits
    probs: jax.Array    # [T, E] float32 scores of all experts (`score`)
    weights: jax.Array  # [T, k] float32 combine weights of the chosen k
    experts: jax.Array  # [T, k] int32 the chosen experts, best first


def _within_groups(biased, n_group: int, topk_group: int):
    """biased [T, E] -> the same with -inf outside the `topk_group` best of
    `n_group` contiguous groups of experts; a group's score is the sum of
    its two best biased scores (DeepSeek-V3's `noaux_tc` form)."""
    t, e = biased.shape
    with jax.named_scope("moe.route_groups"):
        grouped = biased.reshape(t, n_group, e // n_group)
        # the two best as two max passes: `top_k` of [8192, 8, 64] is a
        # full sort on the TPU, 2.5 ms a call on the v5e (PERF.md section
        # 6, PR 39)
        best = jnp.max(grouped, axis=-1)
        first = jnp.argmax(grouped, axis=-1)
        second = jnp.max(jnp.where(
            first[..., None] == jnp.arange(e // n_group), -jnp.inf, grouped),
            axis=-1)
        _, kept = jax.lax.top_k(best + second, topk_group)
        keep = jnp.any(kept[..., None] == jnp.arange(n_group), axis=1)
        device_profiler.count("moe.groups_kept", topk_group)  # per lowering
        return jnp.where(jnp.repeat(keep, e // n_group, axis=1), biased,
                         -jnp.inf)


@jax.custom_jvp
def _formed_first(weights):
    """The k weights first, then whatever reads them: left to itself XLA
    folds the sum over k of `route`'s denominators into the masked sum over
    the experts, ONE reduce over [T, k x E]: another order of summation than
    `top_k`'s values had (a last digit of the denominators, 2e-5 of the SDAR
    cell's loss), and 0.42 ms at 16,384 x 8 x 128 on the v5e where the two
    reduces take 0.08 and 0.01 (PERF.md section 6, PR 44). The barrier
    stands in the value alone, the tangent passes it by: on a cotangent of
    zeros (a share's weights are constants of the backward pass) a barrier
    keeps the router's whole backward alive, two `highest` matmuls a layer
    on zeros (Nemotron's cell -1.1%, Ling's -0.8%, same PR). The fold and
    this barrier are pinned together for the v5e's compiler, with no chip:
    `tests/test_tpu_aot_compile.py::test_route_denominators_as_compiled_for_v5e`
    fails the day either is no longer needed or no longer enough."""
    return jax.lax.optimization_barrier(weights)


@_formed_first.defjvp
def _formed_first_jvp(primals, tangents):
    return _formed_first(*primals), tangents[0]


def route(x, router_w, k: int, norm_topk_prob: bool = False, *,
          score: str = "softmax", bias=None, scale: float = 1.0,
          n_group: int = 1, topk_group: int = 1) -> Routing:
    """x [T, D], router_w [D, E] -> the top-k choice per token, over ALL E
    experts whether or not they are held here. Logits, scores and weights
    are float32 whatever the model dtype: a bf16 logit would flip choices
    between near-equal experts.

    `score` is the scoring function: "softmax" over the experts (Mixtral,
    OLMoE) or an independent "sigmoid" per expert (DeepSeek-V3's form).
    `bias` [E] is added to the scores for the CHOICE only (`noaux_tc`): it
    gets no gradient and the weights are the unbiased scores of the chosen.
    `norm_topk_prob` divides the k weights by their sum (over all k chosen,
    held or not; Mixtral and DeepSeek-V3; OLMoE leaves them as they are);
    `scale` then multiplies them (`routed_scaling_factor`). With `n_group`
    > 1 the choice is made WITHIN GROUPS: the experts are `n_group`
    contiguous groups, the `topk_group` best groups stay (`_within_groups`,
    on the biased scores) and the top-k is taken among their experts."""
    if score not in ("softmax", "sigmoid"):
        raise ValueError(f"unknown scoring function {score!r}")
    if n_group > 1 and (router_w.shape[1] % n_group
                        or not 0 < topk_group <= n_group
                        or k > topk_group * (router_w.shape[1] // n_group)):
        raise ValueError(f"{router_w.shape[1]} experts in {n_group} groups, "
                         f"{topk_group} kept, top-{k}")
    with jax.named_scope("moe.route"):
        logits = jnp.dot(x, router_w, precision=jax.lax.Precision.HIGHEST,
                         preferred_element_type=jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1) if score == "softmax" \
            else jax.nn.sigmoid(logits)
        # the CHOICE alone comes from `top_k`, and no gradient goes through
        # it; the bias is for the choice only
        biased = jax.lax.stop_gradient(
            probs if bias is None else probs + bias.astype(jnp.float32))
        if n_group > 1:
            biased = _within_groups(biased, n_group, topk_group)
        _, experts = jax.lax.top_k(biased, k)
        # the chosen experts' own scores, as a masked sum over the experts
        # (p plus exact zeros, and on the way back one contribution a
        # position): a gather of T x k scalars out of [T, E] and the scatter
        # that transposes it, which is what autodiff makes of `top_k`'s
        # values too, take 0.67 and 0.57 ms on the v5e at 8,192 x 256, three
        # times a layer (PERF.md section 6, PR 32); the masked sum and its
        # transpose 0.08 and 0.12 ms at 16,384 x 128 (PR 44)
        chosen = experts[..., None] == jnp.arange(probs.shape[-1])
        weights = jnp.sum(
            jnp.where(chosen, probs[:, None, :], 0.0), axis=-1)
        if norm_topk_prob:
            weights = _formed_first(weights)
            weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        if scale != 1.0:
            weights = weights * scale
        return Routing(logits, probs, weights, experts.astype(jnp.int32))


def _count_by_expert(experts, n_experts: int):
    """experts [...] int32 -> [n_experts] int32, the entries that chose each
    expert: a comparison against `arange(n_experts)` with the entries along
    the minor axis, and a sum over them. A `bincount` is a scatter-add, and
    a scatter serialises on the TPU: 1.15 ms for 131,072 pairs into 128
    bins on the v5e (PERF.md section 6, PR 44)."""
    device_profiler.count("moe.counts_by_comparison", 1)  # per lowering
    chose = experts.reshape(-1)[None, :] == jnp.arange(n_experts)[:, None]
    return jnp.sum(chose, axis=1, dtype=jnp.int32)


def router_losses(routing: Routing, axis_name=None):
    """-> (load_balance, router_z), one layer's terms, both float32 scalars.

    load_balance = E * sum_i f_i P_i with f_i the share of the T x k
    (token, slot) pairs sent to expert i (a count: no gradient) and P_i the
    mean router probability of expert i; 1 when routing is even. router_z =
    mean_t logsumexp_i(logits)^2. With `axis_name` (inside a shard_map over
    tokens) the statistics are taken over every shard's tokens."""
    t, e = routing.probs.shape
    counts = _count_by_expert(routing.experts, e).astype(jnp.float32)
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
    order = jnp.argsort(experts.reshape(-1), stable=True).astype(jnp.int32)
    inverse = jnp.argsort(order).astype(jnp.int32)
    return order, inverse, _count_by_expert(experts, n_experts)


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


def share_capacities(t: int, k: int, n_held: int, n_experts: int) -> tuple:
    """Static row capacities of a share's buffers, smallest first: twice the
    T x k x n_held / n_experts rows that land here when routing is even
    (rounded up to the grouped matmul's row tile), doubling up to T x k, the
    most that can land (every choice of every token held): nothing is
    dropped at any imbalance. Twice: at seeded weights the live rows of a
    routed block scatter widely around the even share (0.16x to 2.2x over
    ~4,000 blocks of sixteen seeds on the v5e), and 1-4% of a seed's blocks
    run the next capacity (PERF.md section 6, PR 32, on why not 1.25x or
    2.5x, measured while the combine still gathered T x k rows out of the
    buffer). Since PR 45 a capacity sizes the buffers and the elementwise
    work over `[cap, F]` only: the row moves follow the live rows a tile at a
    time (`ops/row_moves.py`), as the grouped matmuls do, so what 1.5x could
    still save is a quarter of that elementwise work, for one more branch to
    trace, lower and load (section 7). Holding every expert gives (T x k,)."""
    rows = t * k
    cap = -(-2 * (rows * n_held // n_experts) // 256) * 256
    caps = []
    while 0 < cap < rows:
        caps.append(cap)
        cap *= 2
    return tuple(caps) + (rows,)


def capacity_load(live, caps):
    """live [...] int32, the live rows of routed blocks -> float32 [...]:
    each block's live rows over the rows of the capacity of `caps` it runs
    at (`_capacity_switch`'s choice). That is the share of the buffer's row
    tiles its row moves visit, to a tile (`ops/row_moves.py`); the rest of
    the buffer is sized and masked, not moved. Outside the train step, for
    `routing_loads` of the models."""
    caps = jnp.asarray(caps)
    index = jnp.sum(live[..., None] >= caps[:-1], axis=-1)
    return live / caps[index]


def sort_held(experts, first_expert: int, n_held: int):
    """experts [T, k] -> (order, inverse, group_sizes): the pairs whose
    expert is in [first_expert, first_expert + n_held) FIRST, by expert
    (stable), then the absent ones. `order[s]` is the pair at sorted
    position s, `inverse[p]` the sorted position of pair p = t * k + j,
    `group_sizes[i]` the pairs of held expert i. Their sum is the LIVE rows:
    the sorted positions below it hold every pair that landed here, so a
    buffer of at least that many rows drops nothing."""
    flat = experts.reshape(-1) - first_expert
    local = jnp.where((flat >= 0) & (flat < n_held), flat, n_held)
    pairs = jnp.arange(local.shape[0], dtype=jnp.int32)
    keys, order = jax.lax.sort_key_val(local, pairs)
    inverse = jax.lax.sort_key_val(order, pairs)[1]
    # counts without a scatter (`_count_by_expert` says why), from the keys
    # the sort has made anyway: where each expert's run starts in them
    starts = jnp.sum(keys[None, :] < jnp.arange(n_held + 1)[:, None], axis=1)
    return order, inverse, jnp.diff(starts).astype(jnp.int32)


# A share's two moves of rows, each the other's transpose (as `_permute` and
# `_combine` are for the whole dispatch): `token[s]` is the token of sorted
# row s, or T for a dead row (past the `live` ones); `slot[t, j]` the row of
# token t's j-th pair, or a dead row for a pair whose expert is absent.
# Both follow the buffer's LIVE rows, not its `cap` rows nor the T x k slots
# (`ops/row_moves.py`, `ops/row_sums.py`; a scatter-add of the live rows into
# their tokens: 1.83 ms for 16,384 rows of 2,048, PERF.md section 6, PR 32).
# No fill is made for a dead row of `_take_rows`' result: up to the end of
# the last live row tile it holds some token's row (the dead marker clamped
# into range: finite), past it what `ops/row_moves.py` leaves, zeros or, on
# a TPU, memory nothing has written. Three readers, and who may read an
# unwritten tile is nobody: the grouped matmuls' `gmm` visits live tiles
# only and `_held_rows` masks its gate and up with `valid`; the weights'
# gradient `tgmm` visits live tiles only and masks a tile's rows outside
# their group; `_sum_rows`, on the way back, reads no dead row.

@jax.custom_vjp
def _take_rows(x, token, slot, live):
    """x [T, D] -> [cap, D]: row s is token `token[s]`; where dead, any
    token's row (finite) up to the last live row tile's end and nothing of
    ours past it: read by nothing that is not masked."""
    return take_live_rows(x, jnp.minimum(token, x.shape[0] - 1), live)


def _take_rows_fwd(x, token, slot, live):
    return _take_rows(x, token, slot, live), (token, slot, live)


def _take_rows_bwd(res, g):
    # the grouped matmuls' backward leaves g's dead rows unwritten
    return _sum_rows(g, *res), None, None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


@jax.custom_vjp
def _sum_rows(rows, token, slot, live):
    """rows [cap, D], anything where dead -> [T, D]: the sum of each token's
    live rows, accumulated in float32, in rows.dtype."""
    return sum_rows_by_token(rows, token, slot, live)


def _sum_rows_fwd(rows, token, slot, live):
    return _sum_rows(rows, token, slot, live), (token, slot, live)


def _sum_rows_bwd(res, g):
    return _take_rows(g, *res), None, None, None


_sum_rows.defvjp(_sum_rows_fwd, _sum_rows_bwd)


# the gated forms' gates: down(gate(w_gate x) * w_up x), three matrices
_GATES = {"swiglu": jax.nn.silu, "reglu": jax.nn.relu}


def _expert_hidden(rows, experts, w_sorted, form: str, gmm):
    """rows [M, D] in sorted order -> what the down projection reads, [M, F]
    in rows.dtype, the combine weights `w_sorted` [M] applied: `form`
    "swiglu" is silu(gate x) * up x (w_gate, w_up), "reglu" relu(gate x) *
    up x (the same matrices under the other gate: SmallThinker's experts),
    "relu2" is relu(up x)^2 (w_up alone: Nemotron-H's experts). Formed in
    float32, rounded once. `gmm(lhs, w)` is the grouped matmul over this
    dispatch's groups."""
    f32 = jnp.float32
    if form in _GATES:
        gate = gmm(rows, experts["w_gate"])
        up = gmm(rows, experts["w_up"])
        h = _GATES[form](gate.astype(f32)) * up.astype(f32)
    elif form == "relu2":
        h = jnp.square(jax.nn.relu(gmm(rows, experts["w_up"]).astype(f32)))
    else:
        raise ValueError(f"unknown expert form {form!r}")
    return (h * w_sorted[:, None]).astype(rows.dtype)


def _held_rows(x, experts, weights, order, inverse, group_sizes, k: int,
               cap: int, form: str = "swiglu"):
    """One capacity's program: the first `cap` sorted pairs (all the live
    ones, and at least one dead row unless every pair is live, or
    `_capacity_switch` would not have chosen it) through the held experts'
    SwiGLU, summed back into their tokens -> [T, D] in x.dtype. Plain ops,
    differentiable in x and the experts' weights: rows past the live ones
    hold no token's pair (`_take_rows` says what they do hold), and the
    kernels visit live tiles only and leave the rest
    unwritten. gate and up are masked there, so that h and, on the way
    back, their gradients are zero where dead (the weights' gradients read
    them); the down projection's result is read by `_sum_rows` alone."""
    t = x.shape[0]
    live = jnp.sum(group_sizes)
    valid = jnp.arange(cap) < live
    pair = order[:cap]
    token = jnp.where(valid, pair // k, t)
    slot = jnp.where(inverse < live, inverse, cap - 1).reshape(t, k)

    def gmm(lhs, w):
        return jnp.where(valid[:, None], grouped_matmul(lhs, w, group_sizes),
                         jnp.zeros((), lhs.dtype))

    with jax.named_scope("moe.permute"):
        rows = _take_rows(x, token, slot, live)
        w_sorted = weights.reshape(-1)[pair]  # dead rows: gate, up are 0
    with jax.named_scope("moe.experts"):
        h = _expert_hidden(rows, experts, w_sorted, form, gmm)
        out = grouped_matmul(h, experts["w_down"], group_sizes)
    with jax.named_scope("moe.combine"):
        return _sum_rows(out, token, slot, live)


def _capacity_switch(caps, group_sizes, branch, *operands):
    """Run `branch(cap)(*operands)` at the smallest capacity that is MORE
    than the live rows (so a dead row is there for absent pairs to point
    at), or at the last, T x k, which holds everything. One capacity: no
    switch in the program."""
    if len(caps) == 1:
        return branch(caps[0])(*operands)
    live = jnp.sum(group_sizes)
    index = sum((live >= c).astype(jnp.int32) for c in caps[:-1])
    return jax.lax.switch(index, [branch(c) for c in caps], *operands)


@partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _held_experts(x, experts, weights, order, inverse, group_sizes, k, caps,
                  form):
    """The routed part of a share: `_held_rows` at the smallest of the
    static capacities `caps` that holds this step's live rows, so buffers
    and elementwise work are sized by what landed here (to a factor of two)
    and the grouped matmuls and the row moves visit live tiles only.

    A `custom_vjp` because of the switch: differentiated by jax, every
    branch would write zeros for every other branch's residuals on each
    step. Here the residuals are the operands, and the backward pass picks
    its capacity as the forward did and differentiates that one program
    (gate and up are recomputed, as remat "dots" recomputes them anyway).
    The combine weights get no gradient (`moe_layer` says why)."""
    return _capacity_switch(
        caps, group_sizes,
        lambda cap: partial(_held_rows, k=k, cap=cap, form=form),
        x, experts, weights, order, inverse, group_sizes)


def _held_experts_fwd(x, experts, weights, order, inverse, group_sizes, k,
                      caps, form):
    return (_held_experts(x, experts, weights, order, inverse, group_sizes,
                          k, caps, form),
            (x, experts, weights, order, inverse, group_sizes))


def _held_experts_bwd(k, caps, form, res, g):
    def branch(cap):
        def grads(x, experts, weights, order, inverse, group_sizes, g):
            _, vjp = jax.vjp(
                lambda x, e: _held_rows(x, e, weights, order, inverse,
                                        group_sizes, k=k, cap=cap, form=form),
                x, experts)
            return vjp(g)
        return grads

    return _capacity_switch(caps, res[5], branch, *res, g) \
        + (None, None, None, None)


_held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


def moe_layer(x, router_w, experts, k: int, norm_topk_prob: bool = False, *,
              score: str = "softmax", router_bias=None,
              weight_scale: float = 1.0, held=None, n_group: int = 1,
              topk_group: int = 1, form: str = "swiglu", rows=None,
              routing: Routing = None):
    """Dropless top-k experts. x [T, D]; router_w [D, E]; `experts` holds
    w_gate, w_up [E', D, F] and w_down [E', F, D]. -> (y [T, D] in
    x.dtype, MoEAux): y_t = sum_j w_tj * down_e(silu(gate_e x_t) * up_e x_t)
    over token t's k experts e, accumulated in float32. The choice and the
    weights w_tj are `route`'s (`score`: softmax or sigmoid scores,
    `router_bias`, `norm_topk_prob`, `weight_scale`, `n_group` and
    `topk_group`), over all E experts.

    `form` "reglu": relu in silu's place; "relu2": the experts are
    down_e(relu(up_e .)^2), two matrices (`_expert_hidden`). `rows` [T, D']:
    what is DISPATCHED where that is not what the router reads (experts in a
    latent: the router sees x at the residual's width, the experts' matrices
    are [E', D', F] and [E', F, D'] and y is [T, D'], in rows.dtype); None: x
    itself.

    `routing`: the choice as `route` formed it EARLIER, from whatever that
    model's router reads (a layer whose router stands before its attention:
    the attention's input, `models/window_moe.py`), over the same T tokens
    and all E experts. Then nothing is routed here: `router_w` and the
    arguments that are `route`'s are not read (pass `router_w=None`), x is
    what is dispatched, and dispatch, experts and combine run as for any
    other caller, whole or a share. Any model may hand one in; `rows=` keeps
    its meaning (a latent beside what the router read) and is not for this.

    The share: `held=None` means every expert is here (E' = E). With
    `held=(first_expert, n_held)` the E' = n_held experts `first_expert ..`
    are (one chip of an expert-parallel deployment): the sum runs over the
    pairs (t, j) whose expert is held, with the weights they have among all
    k chosen; the other chips' part is left out and no exchange is made.
    Nothing that lands here is dropped (`share_capacities`). On a share the
    weights w_tj are CONSTANTS of the backward pass: what reaches them here
    is the held experts' term of a gradient whose other terms (the absent
    experts') this program cannot form, and that term alone says "only held
    experts answer": applied by itself it moved every token of the first
    expert layer onto the held experts within ~15 AdamW steps on the v5e
    (PERF.md section 6, PR 32), a trajectory no deployment has. So a share
    trains its experts and the layers around them, not its router (whose
    `router_losses`, where a model uses them, still do).

    The down projection is linear, so w_tj multiplies its INPUT, on the
    sorted side: silu(gate) * up * w is formed in float32 and rounded to
    x.dtype once. What is left of the combine is then a sum of each token's
    rows with nothing but indices to keep for its transpose. Applied after
    the down projection, the weights' gradient would need its output: remat
    "dots" would rerun the down matmul and the un-permute for it alone."""
    t = x.shape[0]
    if routing is None:
        routing = route(x, router_w, k, norm_topk_prob, score=score,
                        bias=router_bias, scale=weight_scale, n_group=n_group,
                        topk_group=topk_group)
    elif routing.experts.shape != (t, k):
        raise ValueError(f"a routing of {routing.experts.shape} for {t} "
                         f"tokens, top-{k}")
    e = routing.probs.shape[1]
    if rows is None:
        rows = x
    else:
        device_profiler.count("moe.latent_rows", t * k)  # per lowering
    if held is not None:
        first, n_held = held
        if not (0 <= first and first + n_held <= e) \
                or experts["w_down"].shape[0] != n_held:
            raise ValueError(f"held experts {held} of {e}, weights for "
                             f"{experts['w_down'].shape[0]}")
        caps = share_capacities(t, k, n_held, e)
        with jax.named_scope("moe.permute"):
            order, inverse, group_sizes = sort_held(
                routing.experts, first, n_held)
        y = _held_experts(rows, experts, routing.weights, order, inverse,
                          group_sizes, k, caps, form)
        device_profiler.count("moe.experts_held", n_held)
        device_profiler.count("moe.rows_capacity", caps[-1])
        device_profiler.count("moe.combine_slots", t * k * len(caps))
        device_profiler.count("moe.combine_rows", sum(caps))
        for tiles in map(row_tiles, caps):  # which buffers' moves loop
            if tiles > 1:
                device_profiler.count("moe.row_moves_tiled", tiles)
            else:
                device_profiler.count("moe.row_moves_whole", 1)
    else:
        with jax.named_scope("moe.permute"):
            order, inverse, group_sizes = sort_by_expert(routing.experts, e)
            rows = _permute(rows, order, inverse, k)
            w_sorted = _reorder(routing.weights.reshape(-1), order, inverse)
        with jax.named_scope("moe.experts"):
            h = _expert_hidden(
                rows, experts, w_sorted, form,
                lambda lhs, w: grouped_matmul(lhs, w, group_sizes))
            out = grouped_matmul(h, experts["w_down"], group_sizes)
        with jax.named_scope("moe.combine"):
            y = _combine(out, order, inverse, k)
    # per lowering, as `flash.steps_*` are
    device_profiler.count("moe.rows_routed", t * k)
    device_profiler.count("moe.experts", e)
    device_profiler.count("moe.gmm_calls", 3 if form in _GATES else 2)
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
