"""The shape of `ray_tpu/models/`: model modules depend DOWN on the layer
library (`blocks`, `experts`, `mixers`, `streams`, `layer_pattern`) and never
sideways on each other's private names; `layer_pattern.walk` runs a plan as
a plain loop over the held layers would; `experts.live_rows` counts what a
share holds. Tier-1 (tests/test_models.py is the slow tier, whole). The file's
name sorts it LAST: `--dist loadfile` hands files to workers in order, and
a file added in the middle moves what runs beside the one load-sensitive
host-plane test at the end (`test_workflow_dag.py`'s handshake of two
workers: it failed twice of two whole runs with this file named
`test_model_library.py` and passed with it here)."""

import ast
import collections
import os
import zlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import (
    blocks, experts, granite_hybrid, hybrid_moe, nemotron_h, window_moe)
from ray_tpu.models.layer_pattern import at
from ray_tpu.parallel import moe

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = "ray_tpu.models"
MODELS_DIR = os.path.join(REPO, "ray_tpu", "models")
MODULES = sorted(f[:-3] for f in os.listdir(MODELS_DIR)
                 if f.endswith(".py") and f != "__init__.py")
# what a module of the package may import of the package: the library, in
# its own order; a model module the library, and TWO whole-model reuses
# (`MixtralConfig(LlamaConfig)`; `SdarConfig(MixtralConfig)` and
# `mixtral.hidden_states`)
LIBRARY = {"blocks": set(), "layer_pattern": set(), "experts": {"blocks"},
           "mixers": {"blocks"}, "streams": {"blocks"}}
MODEL_EDGES = {"mixtral": {"llama"}, "sdar": {"mixtral"}}


def package_uses(path):
    """-> ({module of the package the file imports}, [(module, private
    name)] the file imports from it or reads off it). The file's own text
    and every string constant that is a program (the AOT tests' scripts)."""
    with open(path) as f:
        trees = [ast.parse(f.read())]
    for node in ast.walk(trees[0]):
        if isinstance(node, ast.Constant) and isinstance(node.value, str) \
                and "\n" in node.value and "ray_tpu" in node.value:
            try:
                trees.append(ast.parse(node.value))
            except SyntaxError:
                pass
    aliases, private = {}, []
    for node in (n for tree in trees for n in ast.walk(tree)):
        if isinstance(node, ast.ImportFrom) and node.module == PACKAGE:
            aliases.update({a.asname or a.name: a.name for a in node.names})
        elif isinstance(node, ast.ImportFrom) and node.module \
                and node.module.startswith(PACKAGE + "."):
            module = node.module[len(PACKAGE) + 1:]
            aliases.setdefault("from " + module, module)
            private += [(module, a.name) for a in node.names
                        if a.name.startswith("_")]
        elif isinstance(node, ast.Import):
            aliases.update({a.asname or a.name: a.name[len(PACKAGE) + 1:]
                            for a in node.names
                            if a.name.startswith(PACKAGE + ".")})
    for node in (n for tree in trees for n in ast.walk(tree)):
        if isinstance(node, ast.Attribute) \
                and isinstance(node.value, ast.Name) \
                and node.value.id in aliases and node.attr.startswith("_") \
                and not node.attr.startswith("__"):
            private.append((aliases[node.value.id], node.attr))
    return set(aliases.values()), private


def violations(models_dir, module):
    imported, private = package_uses(os.path.join(models_dir, module + ".py"))
    allowed = LIBRARY.get(module, set(LIBRARY) | MODEL_EDGES.get(module,
                                                                 set()))
    return sorted(imported - allowed - {module}), \
        [use for use in private if use[0] != module]


@pytest.mark.parametrize("module", MODULES)
def test_a_model_module_depends_down_on_the_library(module):
    """No underscore name of another module of the package is imported or
    read, and no model module is imported but over the two edges named
    above. (At the commit before the library ten modules fail this: all but
    `llama`, `layer_pattern` and `mlp`.)"""
    sideways, private = violations(MODELS_DIR, module)
    assert not sideways, f"{module} imports {sideways}"
    assert not private, f"{module} reaches into {private}"


def _files_that_use_the_package():
    out = []
    for top in ("tests", "tools"):
        for folder, _, files in os.walk(os.path.join(REPO, top)):
            for name in sorted(files):
                path = os.path.join(folder, name)
                if name.endswith(".py"):
                    with open(path) as f:
                        if PACKAGE in f.read():
                            out.append(os.path.relpath(path, REPO))
    return sorted(out)


@pytest.mark.parametrize("path", _files_that_use_the_package())
def test_tests_and_tools_use_public_names_of_the_models(path):
    _, private = package_uses(os.path.join(REPO, path))
    assert not private, f"{path} reaches into {private}"


# --------------------------------------------------------------------------
# `layer_pattern.walk` against a plain loop over the held layers
# --------------------------------------------------------------------------

def _period_of(periods, period, i):
    """-> (which whole period holds published layer i, i's place in it)."""
    (n,) = [n for n, first in enumerate(periods)
            if first <= i < first + period]
    return n, i - periods[n]


def _hybrid_layers(cfg, params):
    dense, loose, periods, _ = cfg.plan()
    taken = collections.Counter()
    for i in cfg.held_layers:
        mla = cfg.is_mla(i)
        name = "dense" if i in dense else "mla" if mla else "kda"
        if i in dense or i in loose:
            stack = params["dense"] if i in dense else params["loose"][name]
            p = at(stack, taken[name])
            taken[name] += 1
        else:
            n, place = _period_of(periods, cfg.period, i)
            p = at(params["periods"][name], n)
            p = p if mla else at(p, place)
        yield dict(mla=mla, dense=i in dense), p


def _window_layers(cfg, params):
    dense, loose, periods, _ = cfg.plan()
    taken = collections.Counter()
    for i in cfg.held_layers:
        attn, mlp = cfg.kind(i)
        if i in dense or i in loose:
            p = at(params["loose"][f"{attn}_{mlp}"], taken[attn, mlp])
            taken[attn, mlp] += 1
        else:
            n, place = _period_of(periods, cfg.period, i)
            p = at(params["periods"][attn], n)
            p = p if attn == "full" else at(p, place)
        yield dict(attn=attn, mlp=mlp), p


def _granite_layers(cfg, params):
    loose, periods, _ = cfg.plan()
    taken = collections.Counter()
    for i in cfg.held_layers:
        kind = cfg.pattern[i]
        if i in loose:
            p = at(params["loose"][kind], taken[kind])
            taken[kind] += 1
        else:
            n, place = _period_of(periods, cfg.period, i)
            p = at(at(params["periods"][kind], n),
                   cfg.pattern[:place].count(kind))
        yield dict(kind=kind), p


def _nemotron_layers(cfg, params):
    taken = collections.Counter()
    for seg in cfg.plan():
        if seg[0] == "one":
            name = nemotron_h.KINDS[seg[1]]
            yield dict(kind=seg[1]), at(params["one"][name], taken[name])
            taken[name] += 1
            continue
        for _ in range(seg[2]):
            for kind in "EM":
                yield dict(kind=kind), at(
                    params["pairs"][nemotron_h.KINDS[kind]], taken["pairs"])
            taken["pairs"] += 1


# every kind of segment: a dense layer, a loose layer before a period, two
# whole periods in ONE scan, a loose layer after
WALKS = {
    "hybrid_moe": (hybrid_moe, hybrid_moe.HybridMoeConfig.tiny(
        layers=(1, 2, 3, 4, 5, 6, 7, 8, 9), dtype=jnp.float32),
        _hybrid_layers,
        [("dense", 1), ("loose", 1), ("periods", 2), ("loose", 1)]),
    "window_moe": (window_moe, window_moe.WindowMoeConfig.tiny(
        layer_types=tuple("full_attention" if i % 4 == 0
                          else "sliding_attention" for i in range(14)),
        heads_per_layer=tuple(4 if i % 4 == 0 else 6 for i in range(14)),
        mlp_layer_types=("dense",) + ("sparse",) * 13,
        layers=(0, 4) + tuple(range(5, 14)), dtype=jnp.float32),
        _window_layers,
        [("dense", 1), ("loose", 1), ("periods", 2), ("loose", 1)]),
    "granite_hybrid": (granite_hybrid,
                       granite_hybrid.GraniteHybridConfig.tiny(
        pattern=("mamba", "mamba", "attention", "mamba") * 4,
        layers=tuple(range(2, 13)), dtype=jnp.float32),
        _granite_layers, [("loose", 2), ("periods", 2), ("loose", 1)]),
    "nemotron_h": (nemotron_h, nemotron_h.NemotronHConfig.tiny(
        dtype=jnp.float32), _nemotron_layers,
        [("one", "M", 0), ("one", "*", 1), ("pairs", 2, 3), ("one", "*", 8),
         ("one", "E", 9), ("one", "M", 10), ("one", "E", 11)]),
}


def _toy_layer(x, p, positions, config, mesh, rules, **kind):
    """A layer in a few exact operations whose result tells WHICH layer's
    parameters it was given (every leaf's mean), as WHAT kind and after
    what; one that holds experts also says so in what it "chooses"."""
    tag = sum(jnp.mean(leaf.astype(jnp.float32))
              for leaf in jax.tree.leaves(p))
    x = x * 0.5 + tag + zlib.crc32(repr(sorted(kind.items())).encode()) % 997
    if "experts" not in p:
        return x, None
    return x, jnp.arange(6).reshape(3, 2) + (tag * 1e4).astype(jnp.int32)


@pytest.mark.parametrize("name", sorted(WALKS))
def test_the_walk_is_a_plain_loop_over_the_held_layers(name, monkeypatch):
    """`forward_hidden` (embed, `layer_pattern.walk`, the final norm)
    against the model's `layer` applied to each held layer in turn, its
    parameters taken out of the stacks by hand: the hidden states and every
    routing layer's choice, bit for bit. The layer is `_toy_layer`: the
    models' own, scanned against unrolled, are compared (to a rounding) in
    their reference tests; here it is who gets which parameters, exactly."""
    module, cfg, layers_of, segments = WALKS[name]
    plan = cfg.plan()
    assert (plan if name == "nemotron_h" else plan[-1]) == segments
    monkeypatch.setattr(module, "layer", _toy_layer)
    # the model's own tree, every leaf drawn apart: no two layers alike
    rng = np.random.default_rng(0)
    params = jax.tree.map(
        lambda leaf: jnp.asarray(rng.standard_normal(leaf.shape),
                                 jnp.float32),
        jax.eval_shape(partial(module.init, cfg), jax.random.PRNGKey(0)))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0,
                              cfg.vocab_size)
    got = module.forward_hidden(params, toks, cfg)
    got, chosen = got if isinstance(got, tuple) else (got, None)
    x, positions = blocks.embed_tokens(params, toks)
    if name == "granite_hybrid":
        x = blocks.scaled(x, cfg.embedding_multiplier)
    want = []
    for kind, p in layers_of(cfg, params):
        x, e = _toy_layer(x, p, positions, cfg, None, None, **kind)
        want += [] if e is None else [e]
    x = blocks.rms_norm(x, params["final_norm"], cfg.norm_eps)
    np.testing.assert_array_equal(got, x)
    if want:
        np.testing.assert_array_equal(chosen, jnp.stack(want))
        assert len({int(e[0, 0]) for e in want}) == len(want)
    else:
        assert chosen is None


# --------------------------------------------------------------------------
# `experts.live_rows`, for the two share models without a `routing_stats`
# --------------------------------------------------------------------------

@pytest.mark.parametrize("module, cfg", [
    (hybrid_moe, hybrid_moe.HybridMoeConfig.tiny(
        layers=(1, 2, 3, 4, 5), n_experts_held=4, first_expert=8)),
    (nemotron_h, nemotron_h.NemotronHConfig.tiny(
        n_experts_held=4, first_expert=4, mtp_depth=0)),
], ids=["hybrid_moe", "nemotron_h"])
def test_live_rows_count_the_held_pairs_of_every_routing_layer(module, cfg):
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 24), 0,
                              cfg.vocab_size)
    chosen = np.asarray(jax.jit(lambda key, t: module.forward_hidden(
        module.init(cfg, key), t, cfg)[1])(jax.random.PRNGKey(2), toks))
    first, n = cfg.held
    want = ((chosen >= first) & (chosen < first + n)).sum(axis=(1, 2))
    assert chosen.shape[1:] == (2 * 24, cfg.experts_per_token) and want.any()
    live = experts.live_rows(jnp.asarray(chosen), cfg)
    np.testing.assert_array_equal(live, want)
    # and over the rows of the capacity each block runs at
    caps = np.asarray(moe.share_capacities(
        2 * 24, cfg.experts_per_token, n, cfg.n_experts))
    np.testing.assert_allclose(
        experts.capacity_loads(live, 2 * 24, cfg),
        [rows / caps[np.sum(rows >= caps[:-1])] for rows in want], rtol=1e-6)
