"""`benchmarks/train_kda_cell.py`: the comparison `train-solar2-1chip`'s
`correct` holds beside the loss (one KDA call of `ops/kda.py` against
`reference_solar2.recurrence`, o and the five gradients, on the layer's own
input, on `fast_decay` and on `long_memory`), at small sizes on the CPU: it
passes the sound call, and it SEES what the loss cannot: a plan that breaks
on a decay without a bound, and a state carried in bf16
(`tools/kda_chip_check.py`'s controls; the chip's readings at the cell's
shape are in PERF.md section 6).
"""

import dataclasses
import json
import os
import sys
from functools import partial

import jax
import jax.numpy as jnp
import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO_ROOT, "tools"))

import kda_chip_check as tool  # noqa: E402
from benchmarks import reference_solar2 as ref  # noqa: E402
from benchmarks import train_kda_cell as cell  # noqa: E402
from ray_tpu.models import solar_open2  # noqa: E402
from ray_tpu.ops import kda as kda_op  # noqa: E402


def _tokens_first(a):
    return jnp.moveaxis(a[0], 0, 1)


def _inputs(kind, s, h=1, d=16, seed=3):
    """`tools/kda_chip_check.py`'s input of that kind, as the reference lays
    it out, and a bf16 cotangent."""
    args, w = tool.inputs(kind, jax.random.PRNGKey(seed), 1, h, s, d)
    return tuple(map(_tokens_first, args)), \
        _tokens_first(w).astype(jnp.bfloat16)


def _cfg(**over):
    model = solar_open2.SolarOpen2Config.tiny(layers=(0, 1, 2, 3), **over)
    fields = dataclasses.asdict(model)
    fields.pop("dtype")
    return {"model": fields, "model_module": "ray_tpu.models.solar_open2",
            "config_class": "SolarOpen2Config",
            "reference_module": "benchmarks.reference_solar2",
            "trainer": {"seq": 128}, "seed": 2**31 + 11}


def test_the_cells_numbers_at_a_tiny_size():
    """`path_errors` as the worker runs it: the first KDA layer the cell
    holds, at the weights of the seed (a seed past 2**31 as well), three
    inputs, six tensors each, all within the cell's limits."""
    errors = cell.path_errors(_cfg())
    assert set(errors) == {"kda_layer_err", "kda_fast_decay_err",
                           "kda_long_memory_err", "kda_errors"}
    assert set(errors["kda_errors"]) == set(cell.LIMITS)
    for name, by_tensor in errors["kda_errors"].items():
        assert tuple(by_tensor) == cell.NAMES
        assert all(0 < v < 1.5e-2 for v in by_tensor.values()), (name,
                                                                 by_tensor)
    assert errors["kda_layer_err"] == max(
        errors["kda_errors"]["layer"].values())
    assert cell.within_limits(errors)
    json.dumps(errors)


@pytest.mark.parametrize("over,ok", [
    ({}, True), ({"layer": float("nan")}, False),
    ({"fast_decay": float("nan")}, False),
    ({"long_memory": float("inf")}, False),
    ({"layer": cell.LIMITS["layer"] * 1.01}, False),
    ({"fast_decay": cell.LIMITS["fast_decay"] * 1.01}, False),
    ({"long_memory": cell.LIMITS["long_memory"] * 1.01}, False)],
    ids=lambda x: "-".join(x) if isinstance(x, dict) else str(x))
def test_a_reading_over_its_limit_or_not_finite_is_not_correct(over, ok):
    readings = {name: 1e-3 for name in cell.LIMITS} | over
    assert cell.within_limits({f"kda_{name}_err": v
                               for name, v in readings.items()}) is ok


def test_the_layers_input_is_the_references_and_long_memory_keeps_q_k_v():
    cfg = _cfg()
    model = solar_open2.SolarOpen2Config(**cfg["model"])
    params = solar_open2.init(model, jax.random.PRNGKey(0))
    inputs, cot = cell.call_inputs(ref, cfg["model"], params,
                                   jax.random.PRNGKey(1), 128)
    q, k, v, g, beta = inputs["layer"]
    assert q.shape == g.shape == cot.shape == (128, model.n_heads,
                                               model.kda_head_dim)
    assert q.dtype == cot.dtype == model.dtype and g.dtype == jnp.float32
    assert beta.shape == (128, model.n_heads)
    assert float(g.max()) < 0 < float(beta.min()) \
        and float(beta.max()) < model.kda_beta_scale
    q1, k1, v1, fast, beta1 = inputs["fast_decay"]
    assert q1 is q and k1 is k and v1 is v and beta1 is beta
    assert -60 <= float(fast.min()) < -50 and -0.1 < float(fast.max()) < 0
    q2, k2, v2, kept, written = inputs["long_memory"]
    assert q2 is q and k2 is k and v2 is v
    assert -2.5e-5 <= float(kept.min()) and float(kept.max()) <= -2e-5
    assert bool(jnp.all(written[:64] == beta[:64])) \
        and bool(jnp.all(written[64:] == 2e-5))


def test_the_comparison_sees_the_bounded_plan_on_a_decay_without_a_bound():
    """g down to -60 a step: the any-decay plan is within the limit, the
    bounded plan in its place (the tool's control) is not finite."""
    args, cot = _inputs("fast_decay", 256)
    kda = partial(kda_op.kda, g_min=None)
    sound = cell.call_errors(kda, ref.recurrence, args, cot)
    assert max(sound.values()) <= cell.LIMITS["fast_decay"], sound
    with tool.bounded_plan():
        broken = cell.call_errors(kda, ref.recurrence, args, cot)
    assert not max(broken.values(), key=lambda v: (v != v, v)) \
        <= cell.LIMITS["fast_decay"], broken


def test_the_comparison_sees_a_bf16_state_on_long_memory():
    """The kernels under the interpreter, 32 chunks of one head: with the
    state a chunk hands the next rounded to bf16 (the tool's control) o, dq
    and dg read several times the sound kernels' error, which stays where
    the chip's is at this length (PERF.md section 6)."""
    args, cot = _inputs("long_memory", 2048)
    kda = partial(kda_op.kda, g_min=None, interpret=True)
    sound = cell.call_errors(kda, ref.recurrence, args, cot)
    assert max(sound.values()) < 8e-3, sound
    with tool.bf16_state():
        rounded = cell.call_errors(kda, ref.recurrence, args, cot)
    for name in ("o", "dq", "dg"):
        assert rounded[name] > 3 * sound[name], (name, sound, rounded)
    assert rounded["o"] > 2e-2
