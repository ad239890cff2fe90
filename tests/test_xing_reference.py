"""`models/mla_moe.py` on a residual path of FOUR streams (`models/streams.py`:
Xing4.0 by config) against the plain reference `benchmarks/reference_xing.py`,
at tiny sizes on the CPU, seeded weights; the connection alone against its
definition; YaRN under MLA against values written out by hand; and the three
library sublayers, whose branch now stands apart from the add, against the
bodies they had before (the jaxpr, equation for equation). The program runs
in float32 here, so that routing cannot flip between the two."""

import dataclasses
import math
import zlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_xing as ref
from benchmarks import train_hc_cell
from ray_tpu._private import device_profiler
from ray_tpu.models import (
    blocks, experts, hybrid_moe, mixers, mla_moe, streams)
from ray_tpu.models.blocks import rms_norm, rope
from ray_tpu.parallel import moe
from tools import hc_chip_check

# float32 against float32-"highest" (tests/test_mla_moe_reference.py's
# reasoning): 1e-6 to 5e-6 measured below; a bfloat16 product anywhere is
# 200x over it
RTOL = ATOL = 2e-5

YARN = {"type": "yarn", "factor": 64, "original_max_position_embeddings": 16,
        "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1}
SHARE = dict(n_experts_held=4, first_expert=4)   # experts 4-7 of 16
WHOLE = dict()


def _model(over=WHOLE, seed=0, **kw):
    """1 dense + 1 expert layer + the MTP block on four streams, YaRN on."""
    kw = {**dict(hc_mult=4, rope_scaling=YARN, rope_theta=10000.0,
                 routed_scaling_factor=2.0, n_layers=2), **over, **kw}
    cfg = mla_moe.MlaMoeConfig.tiny(
        vocab_size=256, dtype=jnp.float32, remat=False, loss_chunk_size=16,
        **kw)
    params = mla_moe.init(cfg, jax.random.PRNGKey(seed))
    key = jax.random.PRNGKey(seed + 100)

    def rescale(path, w):
        # norm scales that are not 1, a router bias that moves choices, and
        # alphas that are not 1: a scale or an alpha in the wrong place shows
        name = path[-1].key
        sub = jax.random.fold_in(
            key, zlib.crc32(jax.tree_util.keystr(path).encode()) % 2**31)
        if name.endswith("norm"):
            return (1.0 + 0.3 * jax.random.normal(sub, w.shape)).astype(w.dtype)
        if name == "router_bias":
            return 0.2 * jax.random.normal(sub, w.shape)
        if name == "alpha":
            return 1.0 + 0.5 * jax.random.uniform(sub, w.shape)
        return w

    params = jax.tree_util.tree_map_with_path(rescale, params)
    return cfg, params, dataclasses.asdict(cfg)


def _tokens(seed, rows=2, seq=24):
    return jax.random.randint(jax.random.PRNGKey(seed), (rows, seq + 1), 0, 256)


def _loss_and_grads(cfg, params, model, toks):
    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(
            lambda p: mla_moe.loss_fn(p, {"tokens": toks}, cfg))(params)
    want = jax.value_and_grad(
        lambda p: ref.loss_value(p, toks[:, :-1], toks[:, 1:], model))(params)
    return got, want


@pytest.mark.parametrize("over", [SHARE, WHOLE], ids=["share", "whole"])
def test_loss_and_gradients_match_reference_on_four_streams(over):
    """`hc_mult` 4, YaRN and the MTP block on: the loss and EVERY leaf of its
    gradient, the connections' phi, alpha and b among them."""
    cfg, params, model = _model(over)
    assert sum(a.size for a in jax.tree.leaves(params)) == cfg.num_params()
    (got, g_got), (want, g_want) = _loss_and_grads(
        cfg, params, model, _tokens(1))
    np.testing.assert_allclose(got, want, rtol=RTOL)
    # a leaf against its own largest entry; the floor is for the FIRST
    # connection's maps, which see four equal streams: H_res X = X whatever
    # H_res is, and H_pre only scales what the sublayer's norm rescales, so
    # their gradients are rounding noise about 0 (1e-9 where the next
    # connection's are 1e-3)
    floor = max(float(jnp.abs(b).max())
                for b in jax.tree.leaves(g_want["layers"]["hc_attn"]))
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g_got),
                            jax.tree.leaves(g_want)):
        scale = max(float(jnp.abs(b).max()), floor)
        np.testing.assert_allclose(a / scale, b / scale, atol=ATOL,
                                   err_msg=jax.tree_util.keystr(path))
    # every map of a connection in the middle of the path is trained (the
    # last one's H_res is not: its columns sum to 1 and the streams' sum
    # follows it)
    assert float(jnp.abs(g_want["dense"]["hc_attn"]["alpha"][0, 0])) \
        < 1e-4 * floor
    for name in ("hc_attn", "hc_mlp"):
        for leaf in jax.tree.leaves(g_got["layers"][name]):
            assert float(jnp.abs(leaf).max()) > 1e-2 * floor, name


@pytest.mark.parametrize("what", ["one_sinkhorn_iteration", "static_maps",
                                  "no_mscale", "plain_frequencies",
                                  "one_stream"])
def test_a_departure_in_the_program_misses_the_reference(what):
    """What the comparison is FOR: each of these, patched into the program's
    side alone, leaves the cell's tolerance (`benchmarks/train_cell.py`'s
    3e-4 of the loss; 7e-4 to 4e-2 here, where the model itself agrees to
    1e-7)."""
    cfg, params, model = _model(SHARE)
    toks = _tokens(1)
    want = ref.loss(params, toks[:, :-1], toks[:, 1:], model)
    if what == "one_sinkhorn_iteration":
        cfg = dataclasses.replace(cfg, hc_sinkhorn_iters=1)
    elif what == "static_maps":       # alpha = 0: the biases alone
        params = jax.tree_util.tree_map_with_path(
            lambda path, w: w * 0 if path[-1].key == "alpha" else w, params)
    elif what == "no_mscale":
        cfg = dataclasses.replace(cfg, rope_scaling={**YARN,
                                                     "mscale_all_dim": 0})
    elif what == "plain_frequencies":
        cfg = dataclasses.replace(cfg, rope_scaling={**YARN, "factor": 1.0001})
    else:  # the streams' first alone: a plain residual path
        cfg = dataclasses.replace(cfg, hc_mult=0)
    with jax.default_matmul_precision("highest"):
        got = float(mla_moe.loss_fn(params, {"tokens": toks}, cfg))
    assert abs(got - want) / want > 3e-4, (got, want)


def test_logits_match_reference_without_the_mtp_block():
    cfg, params, model = _model(SHARE, mtp_depth=0)
    toks = _tokens(2)[:, :-1]
    with jax.default_matmul_precision("highest"):
        got = mla_moe.forward(params, toks, cfg)
    for row_got, row in zip(got, toks):
        np.testing.assert_allclose(row_got, ref.logits(params, row, model),
                                   rtol=RTOL, atol=ATOL)


# ------------------------------------------------------------ the connection

def _connection(seed, b=2, s=24, d=64, n=4, **over):
    cfg = mla_moe.MlaMoeConfig.tiny(hc_mult=n, dtype=jnp.float32, d_model=d,
                                    **over)
    k_p, k_x = jax.random.split(jax.random.PRNGKey(seed))
    return cfg, streams.init_connection(cfg, k_p), \
        jax.random.normal(k_x, (n, b, s, d))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_maps_are_what_the_definition_says(seed):
    """H_pre in (0, 1), H_post in (0, 2); after twenty iterations H_res's
    columns (normalised last) sum to 1 within 1e-5 at every token and its
    rows within 1e-5 at the median token (1e-6; the slowest of 48 stands at
    3e-5, 3e-4 or 4e-3 by the seed: Sinkhorn's rate is the matrix's), where
    ONE iteration leaves the median at 0.1; all three equal to the
    reference's, and H_res is no permutation and no uniform matrix at the
    seeded initialisation: the maps differ by token."""
    cfg, p, X = _connection(seed)
    pre, post, res = streams.maps(X, p, cfg)
    assert pre.shape == post.shape == (4, 2, 24) and res.shape == (4, 4, 2, 24)
    assert float(pre.min()) > 0 and float(pre.max()) < 1
    assert float(post.min()) > 0 and float(post.max()) < 2
    np.testing.assert_allclose(res.sum(0), 1.0, atol=1e-5)
    off = jnp.abs(res.sum(1) - 1)
    assert float(jnp.median(off)) < 1e-5 and float(off.max()) < 1e-2
    once = streams.maps(X, p, dataclasses.replace(cfg, hc_sinkhorn_iters=1))[2]
    assert float(jnp.median(jnp.abs(once.sum(1) - 1))) > 5e-2
    assert 0.05 < float(res.max(axis=(0, 1)).mean()) < 0.95
    assert float(res.std(axis=(2, 3)).min()) > 0.02     # by token
    model = dataclasses.asdict(cfg)
    for row in range(2):
        w_pre, w_post, w_res = ref.hc_maps(
            jnp.moveaxis(X[:, row], 0, 1), p, model)       # [S, n, C]
        np.testing.assert_allclose(pre[:, row].T, w_pre, atol=ATOL)
        np.testing.assert_allclose(post[:, row].T, w_post, atol=ATOL)
        np.testing.assert_allclose(jnp.moveaxis(res[:, :, row], 2, 0), w_res,
                                   atol=ATOL)


def test_the_clamp_is_reached_and_holds():
    """alpha_res = 40 on a ~ N(0, 1): most logits pass +-30, exp(30) and
    exp(-30) meet in one row, and the maps stay finite and doubly stochastic;
    with the clamp at +-100 the same case overflows float32."""
    cfg, p, X = _connection(3)
    p = dict(p, alpha=jnp.array([1.0, 1.0, 40.0]))
    a = jnp.einsum("nbsd,ndm->mbs", X, p["phi"])[8:] * 40.0 / jnp.sqrt(
        jnp.mean(X * X, axis=(0, 3)))
    assert float((jnp.abs(a) > 30).mean()) > 0.3
    res = streams.maps(X, p, cfg)[2]
    assert bool(jnp.isfinite(res).all())
    np.testing.assert_allclose(res.sum(0), 1.0, atol=1e-4)
    loose = dataclasses.replace(cfg, h_res_clamp_min=-100.0,
                                h_res_clamp_max=100.0)
    assert not bool(jnp.isfinite(streams.maps(X, p, loose)[2]).all())
    # and the reference clamps alike
    w_res = ref.hc_maps(jnp.moveaxis(X[:, 0], 0, 1), p,
                        dataclasses.asdict(cfg))[2]
    np.testing.assert_allclose(jnp.moveaxis(res[:, :, 0], 2, 0), w_res,
                               atol=ATOL)


def test_a_connection_is_its_equations_value_and_gradients():
    """X' = H_res X + H_post F(H_pre X) around a branch with weights of its
    own, against the reference's connection a row at a time: the value, and
    the gradients by X, phi, alpha, b and the branch's weight."""
    cfg, p, X = _connection(4)
    w = jax.random.normal(jax.random.PRNGKey(5), (64, 64)) / 8
    model = dataclasses.asdict(cfg)

    def program(X, p, w):
        out, aux = streams.connect(X, p, lambda h: (jnp.tanh(h @ w), 7), cfg)
        assert aux == 7
        return out

    def reference(X, p, w):
        rows = [ref.connection(jnp.moveaxis(X[:, r], 0, 1), p,
                               lambda h: (jnp.tanh(h @ w), None), model)[0]
                for r in range(X.shape[1])]
        return jnp.moveaxis(jnp.stack(rows), 2, 0)         # [n, B, S, C]

    cot = jax.random.normal(jax.random.PRNGKey(6), X.shape)
    with jax.default_matmul_precision("highest"):
        got, vjp = jax.vjp(program, X, p, w)
        want, vjp_ref = jax.vjp(reference, X, p, w)
        np.testing.assert_allclose(got, want, atol=ATOL)
        for a, b in zip(jax.tree.leaves(vjp(cot)),
                        jax.tree.leaves(vjp_ref(cot))):
            scale = float(jnp.abs(b).max())
            np.testing.assert_allclose(a / scale, b / scale, atol=ATOL)


@pytest.mark.parametrize(
    "control", [None, "one_sinkhorn_iteration", "static_maps", "bf16_maps"])
def test_the_cells_comparison_of_a_connection_holds_the_mechanism(control):
    """What `train-xing4-1chip`'s `correct` holds beside the loss
    (`benchmarks/train_hc_cell.py`: the maps, X' and the gradients of one
    connection against the reference's, each under its limit), on bf16
    streams as the cell has them: the program is within every limit, and
    with one Sinkhorn iteration for twenty, with alpha = 0 or with the maps
    in bf16 (`tools/hc_chip_check.py`'s controls, patched into the
    program's side) it misses one."""
    cfg, p, X = _connection(7, b=2, s=48, d=256)
    cfg = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    p["phi"], X = p["phi"].astype(cfg.dtype), X.astype(cfg.dtype)
    cot = jax.random.normal(jax.random.PRNGKey(8), X.shape, cfg.dtype)
    with hc_chip_check.controlled(control):
        errors = train_hc_cell.connection_errors(
            cfg, dataclasses.asdict(cfg), ref, p, X, cot)
    assert train_hc_cell.within_limits(errors) == (control is None), errors
    if control == "bf16_maps":   # the maps' limit alone catches this one
        assert errors["hc_maps_err"] > 10 * train_hc_cell.MAPS_LIMIT
        assert errors["hc_value_err"] <= train_hc_cell.VALUE_LIMIT
    if control is None:
        assert errors["hc_maps_err"] < train_hc_cell.MAPS_LIMIT / 10


def test_the_paths_ends_copy_in_and_sum_out():
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 8, 16))
    X = streams.expand(x, 4)
    assert X.shape == (4, 2, 8, 16)
    np.testing.assert_array_equal(X, jnp.stack([x] * 4))
    np.testing.assert_allclose(streams.reduce(X), 4 * x, rtol=1e-6)
    mesh = type("TpMesh", (), {"shape": {"tp": 2}})()
    with pytest.raises(NotImplementedError, match="tp"):
        streams.connect(X, None, None, None, mesh)


def test_eight_shares_and_the_shared_expert_once_make_the_whole_connection():
    """The guide's share test on four streams: over the 8 shares of one
    expert layer, the routed parts summed with the shared expert counted
    once equal the uncut reference's block, and so X' = H_res X + H_post y
    does (the maps read X alone: every chip forms the same)."""
    cfg, params, model = _model(WHOLE)
    p = jax.tree.map(lambda a: a[0], params["layers"])
    X = jax.random.normal(jax.random.PRNGKey(3), (4, 1, 64, cfg.d_model))
    want, chosen = ref.connection(
        jnp.moveaxis(X[:, 0], 0, 1), p["hc_mlp"],
        lambda h: ref.experts(h, p, model), model)
    per = cfg.n_experts // 8
    with jax.default_matmul_precision("highest"):
        pre, post, res = (
            m[..., None] for m in streams.maps(X, p["hc_mlp"], cfg))
        h = sum(pre[i] * X[i] for i in range(4))
        hn = rms_norm(h, p["mlp_norm"], cfg.norm_eps)
        routed = jnp.zeros_like(h)
        for i in range(8):
            share = dataclasses.replace(cfg, n_experts_held=per,
                                        first_expert=i * per)
            held = dict(p, experts=jax.tree.map(
                lambda a: a[i * per:(i + 1) * per], p["experts"]))
            part, shared, picked = experts.expert_parts(hn, held, share)
            np.testing.assert_array_equal(picked, chosen)
            routed = routed + part
        got = sum(res[:, j] * X[j] for j in range(4)) + post * (routed + shared)
        # and the program's own connection with every expert held

        def block(h):
            r, s, c = experts.expert_parts(
                rms_norm(h, p["mlp_norm"], cfg.norm_eps), p, cfg)
            return r + s, c

        whole, _ = streams.connect(X, p["hc_mlp"], block, cfg)
    want = jnp.moveaxis(want, 1, 0)[:, None]               # [n, 1, S, C]
    np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL)
    np.testing.assert_allclose(whole, want, rtol=RTOL, atol=ATOL)


# ------------------------------------------------------------ YaRN under MLA

def test_yarn_table_and_the_scores_scale_by_hand():
    """Xing4.0's published group on 64 rotary channels at theta 10,000:
    pairs 0-9 turn more than 32 times over 4,096 positions and keep their
    frequency, pairs 23-31 turn less than once and have it divided by 64,
    a linear ramp between (low 10, high 23: 64 ln(4096 / (2 pi t)) /
    (2 ln 10000) = 10.4 at t = 32, 22.5 at t = 1). cos and sin times
    mscale(1) / mscale(1) = 1; the scores times 192^-0.5 (0.1 ln 64 + 1)^2."""
    group = {"type": "yarn", "factor": 64, "mscale": 1, "mscale_all_dim": 1,
             "original_max_position_embeddings": 4096, "beta_fast": 32,
             "beta_slow": 1}
    cfg = mla_moe.MlaMoeConfig(rope_theta=10000.0, rope_scaling=group)
    hash(cfg)                     # the group is held in a hashable form
    rot = cfg.rotary
    assert rot == blocks.Rotary(10000.0, None, (64, 4096, 32, 1), 1.0)
    freq = np.asarray(rot.inv_freq(64), np.float64)
    plain = 10000.0 ** (-np.arange(32) / 32.0)
    np.testing.assert_allclose(freq[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(freq[23:], plain[23:] / 64, rtol=1e-6)
    np.testing.assert_allclose(                    # pair 16: 6/13 up the ramp
        freq[16], plain[16] * (1 - 6 / 13) + plain[16] / 64 * (6 / 13),
        rtol=1e-6)
    np.testing.assert_allclose(freq[16], 0.005454, rtol=1e-3)
    assert cfg.attn_scale == pytest.approx(
        192 ** -0.5 * (0.1 * math.log(64) + 1) ** 2)
    assert cfg.attn_scale == pytest.approx(0.144680, rel=1e-5)
    # the reference's own table is the same numbers
    inv_freq, on_cos_sin, on_scale = ref.rotary_table(dict(
        qk_rope_head_dim=64, rope_theta=10000.0, rope_scaling=group))
    np.testing.assert_allclose(inv_freq, freq, rtol=1e-6)
    assert on_cos_sin == 1.0
    assert on_scale * 192 ** -0.5 == pytest.approx(cfg.attn_scale)
    # mscale != mscale_all_dim: the factor lands on cos and sin
    uneven = mla_moe.MlaMoeConfig(rope_scaling={**group, "mscale": 0.707})
    assert uneven.rotary.attention_factor == pytest.approx(
        (0.0707 * math.log(64) + 1) / (0.1 * math.log(64) + 1))
    plain_cfg = mla_moe.MlaMoeConfig()
    assert plain_cfg.rotary is None and plain_cfg.attn_scale is None


# ---------------------- the three sublayers: the branch apart from the add

def _mla_sublayer_as_it_was(x, p, positions, config, mesh=None, rules=None):
    """`mixers.mla_sublayer` of the parent commit, line for line."""
    c = config
    n_nope, n_lat = c.qk_nope_head_dim, c.kv_lora_rank
    h = rms_norm(x, p["attn_norm"], c.norm_eps)
    with jax.named_scope("mla.latents"):
        up = partial(jnp.einsum, "bsr,rhk->bshk")
        if c.q_lora_rank:
            c_q = rms_norm(h @ p["wq_a"], p["q_norm"], c.norm_eps)
            w_q = p["wq_b"]
        else:
            c_q, w_q = h, p["wq"]

        def head_norm(x, name, rotary):
            if not c.qk_head_norm:
                return x
            scale = mixers.interleaved(p[name][n_nope:], c) if rotary \
                else p[name][:n_nope]
            return rms_norm(x, scale, c.norm_eps)

        turned = lambda x, name: rope(  # noqa: E731
            head_norm(x, name, True), positions, c.rope_theta)
        q = head_norm(up(c_q, w_q[..., :n_nope]), "q_head_norm", False)
        q_rope = turned(up(c_q, mixers.interleaved(w_q[..., n_nope:], c)),
                        "q_head_norm")
        kv_a = h @ jnp.concatenate(
            [p["wkv_a"][:, :n_lat],
             mixers.interleaved(p["wkv_a"][:, n_lat:], c)], axis=-1)
        c_kv = rms_norm(kv_a[..., :n_lat], p["kv_norm"], c.norm_eps)
        k = head_norm(up(c_kv, p["wkv_b"][..., :n_nope]), "k_head_norm",
                      False)
        v = up(c_kv, p["wkv_b"][..., n_nope:])
        k_rope = turned(kv_a[..., None, n_lat:], "k_head_norm")
    with jax.named_scope("mla.attend"):
        attn = blocks.flash(q, k, v, mesh, causal=True, q_rope=q_rope,
                            k_rope=k_rope)
    if c.attn_gate:
        with jax.named_scope("mla.gate"):
            attn = blocks.head_gated(attn, h, p["w_attn_gate"])
    x = x + jnp.einsum("bshk,hkd->bsd", attn, p["wo"])
    return blocks.residual(x, mesh, rules)


def _mlp_sublayer_as_it_was(x, params, config, mesh=None, rules=None):
    """`blocks.mlp_sublayer` of the parent commit off a tp mesh."""
    lc = partial(blocks.with_logical_constraint, mesh=mesh, rules=rules)
    h = rms_norm(x, params["mlp_norm"], config.norm_eps)
    x = x + blocks.scaled(blocks.swiglu(h, params, lc), None)
    return blocks.residual(x, mesh, rules)


def _expert_sublayer_as_it_was(x, p, config, mesh=None, rules=None):
    """`experts.expert_sublayer` of the parent commit."""
    c = config
    b, s, d = x.shape
    h = rms_norm(x, p["mlp_norm"], c.norm_eps)
    rows = h.reshape(b * s, d)
    ahead = experts.routing(rows, p, c)
    routed, aux = moe.moe_layer(rows, None, p["experts"], c.experts_per_token,
                                held=c.held, form="swiglu", routing=ahead)
    if "shared" not in p:
        return blocks.residual(x + routed.reshape(b, s, d), mesh, rules), \
            aux.experts
    with jax.named_scope("moe.shared"):
        sh = p["shared"]
        shared = (jax.nn.silu(h @ sh["w_gate"]) * (h @ sh["w_up"])) \
            @ sh["w_down"]
    x = x + routed.reshape(b, s, d) + shared
    return blocks.residual(x, mesh, rules), aux.experts


def _joyai():
    cfg = mla_moe.MlaMoeConfig.tiny(n_experts_held=4)
    return cfg, mla_moe.init(cfg, jax.random.PRNGKey(0))["layers"]


def _ling():
    cfg = hybrid_moe.HybridMoeConfig.tiny(n_experts_held=4)
    params = hybrid_moe.init(cfg, jax.random.PRNGKey(0))
    stacks = [v for v in jax.tree.leaves(
        params, is_leaf=lambda t: isinstance(t, dict) and "wkv_a" in t)
        if isinstance(v, dict) and "wkv_a" in v and "router" in v]
    return cfg, stacks[0]


@pytest.mark.parametrize("config", [_joyai, _ling], ids=["joyai", "ling"])
@pytest.mark.parametrize("sublayer", ["mla", "mlp", "experts",
                                      "experts_none_shared"])
def test_a_sublayer_with_its_branch_apart_traces_to_the_jaxpr_it_had(
        config, sublayer):
    """With `hc_mult` 0 and no YaRN the cells' programs are the parent's:
    each library sublayer, now `x + <its branch>`, gives the jaxpr of the
    body it had, equation for equation, under JoyAI's config (a q latent)
    and under Ling's (none; a norm and a gate a head)."""
    cfg, stack = config()
    p = jax.tree.map(lambda a: a[0], stack)
    x = jnp.zeros((2, 16, cfg.d_model), cfg.dtype)
    positions = jnp.broadcast_to(jnp.arange(16), (2, 16))
    if sublayer == "mla":
        now = lambda x: mixers.mla_sublayer(x, p, positions, cfg)  # noqa: E731
        was = lambda x: _mla_sublayer_as_it_was(  # noqa: E731
            x, p, positions, cfg)
    elif sublayer == "mlp":
        dense = dict(p, **p["shared"])
        now = lambda x: blocks.mlp_sublayer(x, dense, cfg)  # noqa: E731
        was = lambda x: _mlp_sublayer_as_it_was(x, dense, cfg)  # noqa: E731
    else:
        if sublayer == "experts_none_shared":
            p = {k: v for k, v in p.items() if k != "shared"}
        now = lambda x: experts.expert_sublayer(x, p, cfg)  # noqa: E731
        was = lambda x: _expert_sublayer_as_it_was(x, p, cfg)  # noqa: E731
    assert str(jax.make_jaxpr(now)(x)) == str(jax.make_jaxpr(was)(x))


def test_no_streams_and_no_yarn_is_the_plain_model():
    """`hc_mult` 0 and `rope_scaling` None: no connection in the parameter
    tree, the parameter count JoyAI's, and the loss's jaxpr the one of a
    config that names neither field."""
    named = mla_moe.MlaMoeConfig.tiny(n_experts_held=4, hc_mult=0,
                                      rope_scaling=None)
    plain = mla_moe.MlaMoeConfig.tiny(n_experts_held=4)
    assert named == plain
    params = jax.eval_shape(partial(mla_moe.init, plain),
                            jax.random.PRNGKey(0))
    assert "hc_attn" not in params["layers"]
    assert "hc_attn" not in mla_moe.param_logical_axes(plain)["layers"]
    with_streams = dataclasses.replace(plain, hc_mult=4)
    assert with_streams.num_params() - plain.num_params() == \
        2 * 4 * streams.connection_num_params(4, plain.d_model)
    assert set(mla_moe.param_logical_axes(with_streams)["mtp"]["block"]) \
        >= {"hc_attn", "hc_mlp"}


def test_counters_and_scopes_of_a_lowering_on_four_streams():
    """The names `benchmarks/metrics/hc_*` and PERF.md section 3 go by: per
    lowering, `hc.connections` 2 a layer body traced (the dense layer, the
    scanned expert layers' one body, the MTP block's), `hc.sinkhorn_iters`
    20 a connection, `hc.rows_mixed` tokens x n a connection; the scopes
    `hc.expand`, `hc.maps`, `hc.pre`, `hc.post`, `hc.reduce` in the jaxpr;
    and the shared paths count as they do (`mla.layers`, `mtp.depth`)."""
    cfg, params, _ = _model(SHARE, n_layers=3)
    toks = _tokens(11)
    before = dict(device_profiler.snapshot()["counters"])
    traced = jax.make_jaxpr(
        lambda p: mla_moe.loss_fn(p, {"tokens": toks}, cfg))(params)
    after = device_profiler.snapshot()["counters"]
    delta = {k: after.get(k, 0) - before.get(k, 0) for k in after}
    assert delta["hc.connections"] == 2 * 3
    assert delta["hc.sinkhorn_iters"] == 20 * 2 * 3
    assert delta["hc.rows_mixed"] == 2 * 3 * 4 * toks[:, :-1].size
    assert delta["mla.layers"] == 3 and delta["mtp.depth"] == 1
    assert delta["moe.experts_held"] == 2 * 4

    def scopes(jaxpr):
        for eqn in jaxpr.eqns:
            yield str(eqn.source_info.name_stack)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from scopes(sub)

    entered = set(scopes(traced.jaxpr))
    for name in ("hc.expand", "hc.maps", "hc.pre", "hc.post", "hc.reduce",
                 "mtp.block/hc.expand", "mla.attend", "moe.shared"):
        assert any(name in s for s in entered), name
