"""Sharded training-step builder: params + optimizer over a mesh, one jit.

The per-worker inner loop of JaxTrainer (SURVEY §7): shardings from the
model's logical axes, params initialised directly into sharded buffers (jit
with out_shardings: no host-side full copy), and a donated-buffer train step;
optimizer state inherits the parameter shardings (ZeRO-style).

The step keeps a record of every step (`device_profiler.StepCadence`): once
the dispatch has returned, a mark reads two clocks, `/proc/stat`'s first line,
`getrusage` and the gc / jit listeners' totals, and never waits for the
device; a step far over the median names what ran beside it (a stall record).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu._private.device_profiler import StepCadence, span
from ray_tpu.parallel.sharding import (
    LogicalAxisRules, logical_sharding, param_shardings)


@dataclasses.dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: Any  # int32 scalar array


def _as_dict(state: "TrainState") -> Dict[str, Any]:
    # NOT dataclasses.asdict: that deep-copies leaves, and jax Devices inside
    # NamedShardings (and donated arrays) must not be copied.
    return {"params": state.params, "opt_state": state.opt_state,
            "step": state.step}


def init_train_state(
    init_fn: Callable[[Any], Any],     # key -> params pytree
    optimizer,                          # optax GradientTransformation
    param_logical_axes,
    mesh,
    key,
    rules: Optional[LogicalAxisRules] = None,
) -> Tuple[TrainState, Any]:
    """Initialize params+opt state directly into their shardings.

    Returns (state, state_shardings) — the latter for use as jit shardings.
    """
    with span("train.init_state"):  # its jits: `jit.*` records of their own
        rules = rules or LogicalAxisRules()
        p_shardings = param_shardings(param_logical_axes, mesh, rules)
        params_shape = jax.eval_shape(init_fn, key)
        # Optimizer state shardings: optax states embed params-shaped
        # subtrees (mu/nu/trace...); match them STRUCTURALLY — any subtree
        # with the params' treedef takes the params' shardings wholesale.
        # (Matching by leaf shape/dtype would silently collide when two params
        # share a shape but different shardings.) All else is replicated.
        opt_shape = jax.eval_shape(lambda p: optimizer.init(p), params_shape)
        replicated = logical_sharding(mesh, (), rules)
        p_treedef = jax.tree.structure(params_shape)

        def map_opt(node):
            if jax.tree.structure(node) == p_treedef:
                return p_shardings
            one_level = jax.tree_util.default_registry.flatten_one_level(node)
            if one_level is None:  # leaf
                return replicated
            children, _aux = one_level
            # One-level treedef: every child is a leaf from this vantage point.
            treedef = jax.tree.structure(node, is_leaf=lambda x: x is not node)
            return jax.tree.unflatten(treedef, [map_opt(c) for c in children])

        o_shardings = map_opt(opt_shape)
        state_shardings = TrainState(
            params=p_shardings, opt_state=o_shardings, step=replicated
        )

        def _init(key):
            params = init_fn(key)
            return {
                "params": params,
                "opt_state": optimizer.init(params),
                "step": jnp.zeros((), dtype=jnp.int32),
            }

        init_jit = jax.jit(
            lambda k: _init(k),
            out_shardings=_as_dict(state_shardings),
        )
        # jit out_shardings wants a matching pytree structure; use dict form.
        # (Partitionable threefry, the jax default, makes these draws
        # independent of the output sharding: one seed gives the same params
        # on every mesh — what 1<->n-device loss parity and cross-mesh
        # checkpoint resume rest on.)
        state_dict = init_jit(key)
        state = TrainState(**state_dict)
        return state, state_shardings


def make_train_step(
    loss_fn: Callable,                 # (params, batch) -> scalar loss
    optimizer,
    state_shardings: TrainState,
    batch_sharding=None,
    donate: bool = True,
):
    """Compile (state, batch) -> (state, metrics) with state donation."""

    def step_fn(state_dict: Dict[str, Any], batch):
        params = state_dict["params"]
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        updates, new_opt = optimizer.update(
            grads, state_dict["opt_state"], params
        )
        import optax

        new_params = optax.apply_updates(params, updates)
        gnorm = optax.global_norm(grads)
        metrics = {"loss": loss, "grad_norm": gnorm,
                   "step": state_dict["step"] + 1}
        return {
            "params": new_params,
            "opt_state": new_opt,
            "step": state_dict["step"] + 1,
        }, metrics

    if donate and jax.default_backend() == "cpu":
        # XLA CPU's thunk runtime races donated input buffers in
        # executables DESERIALIZED from the persistent compilation cache
        # (JAX_COMPILATION_CACHE_DIR): stepping a restored checkpoint
        # produced nondeterministic losses in ~40% of fresh processes on
        # this host. In-process-compiled donating programs are fine, the
        # cache without donation is fine, and
        # --xla_cpu_use_thunk_runtime=false is fine — the triple is the
        # bug. Donation only matters for accelerator HBM; CPU forgoes it.
        donate = False
    shardings_dict = _as_dict(state_shardings)
    jitted = jax.jit(
        step_fn,
        in_shardings=(shardings_dict, batch_sharding),
        out_shardings=(shardings_dict, None),
        donate_argnums=(0,) if donate else (),
    )

    def wrapped(state: TrainState, batch) -> Tuple[TrainState, Dict[str, Any]]:
        # host time of the dispatch (jit returns before the device ends)
        with span("train.step.dispatch") as dispatch:
            out, metrics = jitted(_as_dict(state), batch)
        cadence.mark(*dispatch.ends)   # while the device runs the step
        return TrainState(**out), metrics

    cadence = StepCadence()   # below `wrapped`: the lines above stay put
    wrapped.lower = lambda state, batch: jitted.lower(_as_dict(state), batch)
    return wrapped
