"""What the installed libtpu's compiler accepts, checked with no chip.

`jax.experimental.topologies` describes a v5e 2x2 host to the compiler, so
the Pallas kernels and the serving decode program are compiled for
`TPU v5 lite` here, on the CPU sandbox — a reading of the compiler, not of
a run (chip_smoke.py is the run). Two subprocesses, one after the other, do
all of it (the second with the compiler's dump on, for `ops/kda.py`'s
kernels alone): libtpu is loaded there, not into the test process.

It also pins the flash kernels' sequence limit (the kernels keep whole-
sequence K/V, or q/dO, blocks in VMEM; see ops/flash_attention.py): the
BACKWARD pass was refused at S 8192 until dk/dv took lse and delta as rows,
and compiles there now, as at S 4096, under the compiler's default VMEM
limit, every call lowering to the text it always had; at S 16384 all three
kernels were refused (16.75M of the 16.00M a kernel gets) until PR 50 had a
call whose blocks are past the default state its own limit, and compile
there now, under the window rule and causal.
"""

import json
import os
import re
import subprocess
import sys

import pytest

pytest.importorskip("libtpu")

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_HEAD = r"""
import dataclasses
import functools
import hashlib
import json
import os
import re
import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import topologies
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.inference.paged_engine import PagedInferenceEngine
from ray_tpu.models import (
    blocks, llama, mixers, mla_moe, nemotron_h, sdar, window_moe)
from ray_tpu.ops import grouped_matmul, row_moves, row_sums
from ray_tpu.ops.flash_attention import (
    BlockDiffusion, EvaWindows, SlidingWindow, block_schedule,
    flash_attention)
from ray_tpu.parallel import moe
from ray_tpu.parallel.mesh import MeshConfig, build_mesh
from ray_tpu.parallel.sharding import logical_sharding, param_shardings
from tools import step_lowering_hash

topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
one_chip = NamedSharding(Mesh(np.array(topo.devices[:1]), ("x",)), P())


def spec(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def on_chip(tree):
    return jax.tree.map(lambda x: spec(x.shape, x.dtype), tree)


def pair_scatters(hlo, pairs):
    # the ` scatter(` instructions of a compiled program one of whose
    # operands (the array scattered into, the indices, the updates) has
    # `pairs` elements; an instruction's line names its operands only, so
    # their shapes come from the lines that define them
    elements, hits = {}, []
    for ln in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = \w+\[([\d,]*)\]", ln)
        if m:
            elements[m[1]] = int(np.prod([int(n) for n in m[2].split(",") if n]))
        m = re.search(r" scatter\(([^)]*)\)", ln)
        if m and pairs in [elements.get(a.strip()) for a in m[1].split(",")]:
            hits.append(ln.strip()[:200])
    return hits


def flash_operands(hlo):
    # per Pallas call of a compiled program, the shapes of its 4-d operands
    # (q, K, V, dO, dk/dv's rows of lse and delta; a loop plan's table of
    # steps is 2-d, dq's lse and delta 5-d); an instruction's line names its
    # operands only, as in `pair_scatters`
    shapes, calls = {}, []
    for ln in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = (\w+\[[\d,]*\])", ln)
        if m:
            shapes[m[1]] = m[2]
        m = re.search(r' custom-call\(([^)]*)\), custom_call_target='
                      r'"tpu_custom_call"', ln)
        if m:
            calls.append([x for x in (
                shapes.get(re.sub(r"/\*.*?\*/", "", a).strip())
                for a in m[1].split(",")) if x and x.count(",") == 3])
    return calls


def repeats(hlo, elements):
    # the ` broadcast(` instructions of a compiled program, fused ones too,
    # that copy an array to `elements` bf16 elements (a scalar's broadcast,
    # `dimensions={}`, is no copy of an array)
    return [ln.strip()[:160] for ln in hlo.splitlines()
            if (m := re.match(r"\s*(?:ROOT )?%[\w.\-]+ = bf16\[([\d,]+)\]\S* "
                              r"broadcast\(", ln))
            and "dimensions={}" not in ln
            and np.prod([int(n) for n in m[1].split(",")]) == elements]


def computations_of(hlo):
    # the instructions of each computation of a compiled program, by name
    computations, name = {}, None
    for ln in hlo.splitlines():
        m = re.match(r"^(?:ENTRY )?%([\w.\-]+) \(.*\{$", ln)
        if m:
            name = m[1]
            computations[name] = []
        elif ln.startswith("}"):
            name = None
        elif name:
            computations[name].append(ln)
    return computations


def loop_bodies(computations):
    # the instructions of every `while` body of a compiled program
    return [computations[body] for lines in list(computations.values())
            for w in lines
            for body in re.findall(r" while\(.*body=%([\w.\-]+)", w)]


def called_as(computations):
    # computation -> the ways every computation above it is called, up to
    # the entry: "body" of a while, "branch_computations" of a conditional,
    # "calls" of a fusion
    above = {}
    for caller, lines in computations.items():
        for ln in lines:
            for how, names in re.findall(
                    r"(calls|body|condition|to_apply|branch_computations)="
                    r"\{?((?:%[\w.\-]+(?:, )?)+)", ln):
                for name in re.findall(r"%([\w.\-]+)", names):
                    above.setdefault(name, set()).add((caller, how))

    def ways(name, seen=()):
        return {how for caller, how in above.get(name, ())
                if caller not in seen} | {
            w for caller, _ in above.get(name, ()) if caller not in seen
            for w in ways(caller, seen + (name,))}
    return ways


def same_program(hlo):
    # a compiled program's text less what names the checkout and the lines
    # of its sources: the tables of files and stack frames above the first
    # computation, each instruction's metadata, a Pallas call's payload
    lines = hlo.splitlines()
    start = next(n for n, ln in enumerate(lines)
                 if re.match(r"^(ENTRY )?%[\w.\-]+ \(", ln))
    return hashlib.sha256("\n".join(
        re.sub(r'(custom_call_target="tpu_custom_call").*', r"\1",
               re.sub(r", metadata=\{[^}]*\}", "", ln))
        for ln in lines[:1] + lines[start:]).encode()).hexdigest()


out = {"device_kind": topo.devices[0].device_kind}
bf16 = jnp.bfloat16
"""

_SCRIPT = r"""
def flash_grads(q, k, v):
    # use_pallas=True: the default follows jax.default_backend(), cpu here
    return jax.grad(
        lambda q, k, v: flash_attention(q, k, v, use_pallas=True)
        .astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)


lowered = jax.jit(flash_grads).lower(
    spec((4, 2048, 32, 128), bf16), spec((4, 2048, 8, 128), bf16),
    spec((4, 2048, 8, 128), bf16))
out["flash_custom_calls"] = lowered.as_text().count("tpu_custom_call")
# train-1chip's call as lowered, each kernel's assembly without locations
out["flash_s2048_lowered"] = step_lowering_hash.digest(lowered.as_text())
out["flash_s2048_vmem_limits"] = re.findall(
    r"vmem_limit_bytes=(\d+)", str(jax.make_jaxpr(flash_grads)(
        spec((4, 2048, 32, 128), bf16), spec((4, 2048, 8, 128), bf16),
        spec((4, 2048, 8, 128), bf16))))
hlo = lowered.compile().as_text()
out["flash_s2048"] = "compiled"
out["flash_s2048_operands"] = flash_operands(hlo)
out["flash_s2048_repeats"] = repeats(hlo, 4 * 32 * 2048 * 128)

# MLA's call (train-joyai-1chip): keys 192 wide, values 128
lowered = jax.jit(flash_grads).lower(
    spec((4, 2048, 32, 192), bf16), spec((4, 2048, 32, 192), bf16),
    spec((4, 2048, 32, 128), bf16))
out["flash_mla_custom_calls"] = lowered.as_text().count("tpu_custom_call")
lowered.compile()
out["flash_mla"] = "compiled"

# the same call as `mla_moe` makes it, IN PARTS (q, rotary q, k, ONE rotary
# key, v): one checkpointed `mixers.mla_sublayer` at train-joyai-1chip's widths
# and batch, value and gradient, as the v5e's compiler leaves it
cfg = mla_moe.MlaMoeConfig(max_seq_len=2048)
layer_p = jax.eval_shape(lambda: mla_moe.init(
    dataclasses.replace(cfg, vocab_size=8, n_layers=1, d_ff=8),
    jax.random.PRNGKey(0)))["dense"]
layer = blocks.checkpointed(functools.partial(
    mixers.mla_sublayer, config=cfg,
    positions=jnp.broadcast_to(jnp.arange(2048), (4, 2048))), cfg)
# use_pallas=True: `mla_moe` too follows jax.default_backend()
blocks.flash_attention = functools.partial(flash_attention, use_pallas=True)
hlo = jax.jit(jax.value_and_grad(
    lambda x, p: layer(x, p).astype(jnp.float32).sum(), argnums=(0, 1))).lower(
        spec((4, 2048, cfg.d_model), bf16),
        on_chip(jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), layer_p))
    ).compile().as_text()
ops = [ln.strip() for ln in hlo.splitlines()]
out["mla_parts_calls"] = [
    re.match(r"%[\w.\-]+ = (.*?) custom-call\(", ln)[1] for ln in ops
    if 'custom_call_target="tpu_custom_call"' in ln]
for name in ("mla_flash_fwd_roofline", "mla_flash_bwd_roofline"):
    with open(os.path.join(os.environ["REPO_ROOT"], "benchmarks", "metrics",
                           name + ".json")) as f:
        query = re.compile(json.load(f)["trace_query"]["op"])
    out[name + "_events"] = sum(1 for ln in ops if query.search(ln))
out["mla_parts_wide_ops"] = [
    ln[:160] for ln in ops
    if re.match(r"(ROOT )?%[\w.\-]+ = bf16\[4,32,2048,192\]", ln)
    and not re.search(r" (custom-call|parameter|get-tuple-element)\(", ln)]

for name, shape in (("flash_s3584", (2, 3584, 32, 128)),
                    ("flash_s4096", (2, 4096, 32, 128)),
                    ("flash_s8192", (1, 8192, 8, 128))):
    try:
        jax.jit(flash_grads).lower(*[spec(shape, bf16)] * 3).compile()
        out[name] = "compiled"
    except Exception as e:  # noqa: BLE001 - a refusal is the finding
        out[name] = str(e)[:300]


def gmm_grads(lhs, rhs, sizes):
    # _gmm_tpu: `grouped_matmul` follows jax.default_backend(), cpu here
    return jax.value_and_grad(
        lambda lhs, rhs: grouped_matmul._gmm_tpu(lhs, rhs, sizes)
        .astype(jnp.float32).sum(), argnums=(0, 1))(lhs, rhs)


# train-olmoe-1chip's shapes: 8,192 tokens x top-8 rows, 64 experts
for name, (k, n) in (("gmm_up", (2048, 1024)), ("gmm_down", (1024, 2048))):
    lowered = jax.jit(gmm_grads).lower(
        spec((65536, k), bf16), spec((64, k, n), bf16), spec((64,), jnp.int32))
    out[name + "_custom_calls"] = lowered.as_text().count("tpu_custom_call")
    lowered.compile()
    out[name] = "compiled"


def moe_layer_grads(x, router_w, experts):
    # one layer as the cell's scan body runs it: under remat "dots", and
    # WITH `router_losses`' two terms at the cell's coefficients, or the
    # load-balance term's counts are dead code
    def terms(x, router_w, experts):
        y, aux = moe.moe_layer(x, router_w, experts, 8)
        return y, aux.load_balance, aux.router_z

    layer = jax.checkpoint(
        terms, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)

    def loss(*a):
        y, load_balance, router_z = layer(*a)
        return (y.astype(jnp.float32).sum() + 0.01 * load_balance
                + 0.001 * router_z)

    # the value too, or the forward pass is dead code
    return jax.value_and_grad(loss, argnums=(0, 1, 2))(x, router_w, experts)


# `moe_layer` too follows jax.default_backend() through `grouped_matmul`
moe.grouped_matmul = grouped_matmul._gmm_tpu
hlo = jax.jit(moe_layer_grads).lower(
    spec((8192, 2048), bf16), spec((2048, 64), bf16),
    {"w_gate": spec((64, 2048, 1024), bf16),
     "w_up": spec((64, 2048, 1024), bf16),
     "w_down": spec((64, 1024, 2048), bf16)}).compile().as_text()
out["moe_layer_program"] = same_program(hlo)
# the OPTIMIZED program: after the compiler's own dead-code removal
out["moe_layer_custom_calls"] = hlo.count('custom_call_target="tpu_custom_call"')
out["moe_layer_row_gathers"] = len(re.findall(
    r"= bf16\[65536,2048\]\S* gather\(", hlo))
out["moe_layer_pair_scatters"] = pair_scatters(hlo, 65536)
# the count itself is there (forward, and again under remat): the compare
# against the experts' numbers, summed over the pairs
out["moe_layer_counts"] = len(re.findall(
    r"= s32\[64\]\S* reduce\(.*op_name=\"[^\"]*reduce_sum", hlo))

# train-4chip's step (forward + backward, 2 of its 11 layers, its widths
# and batch) over fsdp 2 x tp 2 of the described 2x2. Off the chip flash
# attention takes its jax.numpy branch; the rest is the cell's program.
mesh = build_mesh(MeshConfig(dp=1, fsdp=2, tp=2), devices=topo.devices)
cfg = llama.LlamaConfig(
    vocab_size=32768, d_model=4096, n_layers=2, n_heads=32, n_kv_heads=8,
    d_head=128, d_ff=14336, rope_theta=1e6, max_seq_len=2048,
    loss_chunk_size=1024)
shapes = jax.eval_shape(lambda: llama.init(cfg, jax.random.PRNGKey(0)))
params = jax.tree.map(
    lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s), shapes,
    param_shardings(llama.param_logical_axes(cfg), mesh))
tokens = jax.ShapeDtypeStruct(
    (16, 2048), jnp.int32, sharding=logical_sharding(mesh, ("batch", "seq")))
hlo = jax.jit(jax.value_and_grad(
    lambda p, b: llama.loss_fn(p, b, cfg, mesh))).lower(
        params, {"inputs": tokens, "targets": tokens}).compile().as_text()
# the top-level instructions of every `while` body: the scanned layers'
# forward and backward (and chunked_ce's one, its gradient formed with its
# logits)
computations = computations_of(hlo)
loops = loop_bodies(computations)
in_loops = [ln for body in loops for ln in body]
out["tp_loops"] = len(in_loops) > 0
out["tp_all_reduces_in_loops"] = [
    ln.strip()[:160] for ln in in_loops
    if re.search(r"= bf16\[8,2048,4096\]\S* all-reduce\(", ln)]
out["tp_reduce_scatters_in_loops"] = sum(
    1 for ln in in_loops if re.search(
        r"= bf16\[8,1024,4096\]\S* (fusion\(.*calls=%all-reduce-scatter"
        r"|reduce-scatter\()", ln))
out["tp_permutes_in_loops"] = sum(
    1 for ln in in_loops if re.search(
        r"= \(bf16\[8,1024,4096\]\S*, .* collective-permute-start\(", ln))
out["tp_all_to_alls"] = len(re.findall(r" all-to-all\(", hlo))
# the head's loops: those with lm_head's [., 32768 / tp] in them
head_loops = [body for body in loops
              if any(re.search(r"\[[\d,]*16384\]", ln) for ln in body)]
out["tp_head_loops"] = len(head_loops)
out["tp_head_loop_collectives"] = sum(
    1 for body in head_loops for ln in body if re.search(
        r" (all-gather|all-reduce|reduce-scatter|all-to-all"
        r"|collective-permute-start)\(|calls=%all-reduce-scatter", ln))

# the embedding table's gradient in that step: the scatter-add autodiff
# makes of the lookup (a mesh of four devices), and the table's all-gathers
out["tp_table_scatters"] = len(pair_scatters(hlo, 32768 * 4096))
out["tp_table_all_gathers"] = len(re.findall(
    r"= bf16\[32768,4096\]\S* all-gather\(", hlo))
out["tp_sum_kernels"] = len(re.findall(r"%tgmm[\w.]* = ", hlo))

# the embedding lookup's gradient alone at train-smallthinker-1chip's shape
# (T 16,384 rows, V 37,984, D 2,560) on one chip: off the chip the backward
# rule keeps autodiff's scatter-add (`row_sums.sums_in_order` follows
# jax.default_backend(), cpu here), with the platform's test taken out it
# is the sorted sum; and at train-1chip's (8,192 rows, V 32,768, D 4,096: a
# power of two), where the scatter-add stands on the chip too
on_tpu = lambda dtype: dtype == bf16
for name, sorts, (n_rows, vocab, width) in (
        ("scatter", row_sums.sums_in_order, (16384, 37984, 2560)),
        ("sorted", on_tpu, (16384, 37984, 2560)),
        ("whole_lanes", on_tpu, (8192, 32768, 4096))):
    kept, row_sums.sums_in_order = row_sums.sums_in_order, sorts
    hlo = jax.jit(jax.grad(lambda table, tokens, w: jnp.sum(
        blocks.embed_rows(table, tokens).astype(jnp.float32) * w))).lower(
            spec((vocab, width), bf16), spec((1, n_rows), jnp.int32),
            spec((1, n_rows, width), jnp.float32)).compile().as_text()
    row_sums.sums_in_order = kept
    out["embed_grad_" + name] = {
        "table_scatters": len(pair_scatters(hlo, vocab * width)),
        "kernels": [m[1] for ln in hlo.splitlines() if (m := re.match(
            r"\s*(?:ROOT )?%(\w+)[\w.]* = (\w+\[[\d,]*\])\S* custom-call\(.*"
            r'custom_call_target="tpu_custom_call"', ln))],
        "row_gathers": len(re.findall(
            r"= bf16\[%d,%d\]\S* gather\(" % (n_rows, width), hlo)),
        "sorts": len(re.findall(r" sort\(", hlo))}

# the head alone at train-smallthinker-1chip's widths (S 16,384, D 2,560,
# V 37,984, chunks of 1,024), value and gradient: the matrix products in
# its loops, a fusion's own among them, by the shape they put out
hlo = jax.jit(jax.value_and_grad(
    lambda h, w, t: blocks.chunked_ce(h, w, t, chunk=1024),
    argnums=(0, 1))).lower(
        spec((1, 16384, 2560), bf16), spec((2560, 37984), bf16),
        spec((1, 16384), jnp.int32)).compile().as_text()
computations = computations_of(hlo)
out["head_loop_products"] = sorted(
    m[1] for body in loop_bodies(computations) for ln in body
    for inner in [ln] + [
        x for name in re.findall(r" fusion\(.*calls=%([\w.\-]+)", ln)
        for x in computations[name]]
    if (m := re.match(r"\s*(?:ROOT )?%[\w.\-]+ = (\w+\[[\d,]*\])\S* "
                      r"convolution\(", inner)))

# the flash call under the block-diffusion rule (train-sdar-1chip): q
# [4, 4096, 32, 128] over the concatenation [x_t ; x_0] of 2 x 2,048, 4 kv
# heads, forward and backward
rule = BlockDiffusion(2048, 4)
bd_grads = jax.grad(
    lambda q, k, v: flash_attention(q, k, v, use_pallas=True, mask=rule)
    .astype(jnp.float32).sum(), argnums=(0, 1, 2))
bd_shapes = (spec((4, 4096, 32, 128), bf16), spec((4, 4096, 4, 128), bf16),
             spec((4, 4096, 4, 128), bf16))
lowered = jax.jit(bd_grads).lower(*bd_shapes)
out["flash_bd_custom_calls"] = lowered.as_text().count("tpu_custom_call")
out["flash_bd_vmem_limits"] = re.findall(
    r"vmem_limit_bytes=(\d+)", str(jax.make_jaxpr(bd_grads)(*bd_shapes)))
out["flash_bd_plans"] = {
    name: [plan.static, plan.steps_unmasked, plan.steps_masked,
           plan.steps_diagonal, plan.steps_triangle]
    for name, plan in block_schedule(4096, 4096, 512, 512, rule).items()}
# the causal calls compiled above ([4, 32 / 8, 2048, 128], the call in parts
# at [4, 32, 2048, 128 + 64]): their diagonal tiles as triangle steps
out["flash_s2048_plans"] = {
    name: [plan.static, plan.steps_unmasked, plan.steps_masked,
           plan.steps_triangle]
    for name, plan in block_schedule(2048, 2048, 512, 512, True).items()}
out["flash_causal_dkv_static"] = {
    str(s): block_schedule(s, s, 512, 512, True)["dkv"].static
    for s in (2048, 3584, 4096, 8192)}
dense = re.compile(r"\[(\d+,)*4096,4096\]")
hlo = lowered.compile().as_text()
out["flash_bd_dense"] = [ln.strip()[:160] for ln in hlo.splitlines()
                         if dense.search(ln)]
out["flash_bd"] = "compiled"
out["flash_bd_operands"] = flash_operands(hlo)
out["flash_bd_repeats"] = repeats(hlo, 4 * 32 * 4096 * 128)

# ONE layer of the cell's model at its widths and batch (the share: 16 of
# 128 experts), the whole objective, value and gradient, as the v5e's
# compiler leaves it. `blocks.flash` too follows jax.default_backend(), and
# so does a share's combine (`ops/row_sums.py`)
blocks.flash_attention = functools.partial(flash_attention, use_pallas=True)
moe.sum_rows_by_token = lambda rows, token, slot, live: (
    row_sums._sum_in_token_order(rows, token, live, slot.shape[0]))
row_moves._buffer, row_moves._placed = row_moves._unwritten, row_moves._copied_in
cfg = sdar.SdarConfig(
    vocab_size=18992, d_model=2048, n_layers=1, n_heads=32, n_kv_heads=4,
    d_head=128, d_ff=768, n_experts=128, n_experts_held=16,
    experts_per_token=8, rope_theta=1e6, norm_eps=1e-6, max_seq_len=4096,
    loss_chunk_size=1024)
tokens = spec((4, 2048), jnp.int32)
hlo = jax.jit(jax.value_and_grad(lambda p, b: sdar.loss_fn(p, b, cfg))).lower(
    on_chip(jax.eval_shape(lambda: sdar.init(cfg, jax.random.PRNGKey(0)))),
    {"inputs": tokens, "targets": tokens}).compile().as_text()
ops = [ln.strip() for ln in hlo.splitlines()]
out["sdar_custom_calls"] = sum(
    1 for ln in ops if 'custom_call_target="tpu_custom_call"' in ln)
out["sdar_dense"] = [ln[:160] for ln in ops if dense.search(ln)]
# T x k = 131,072 (token, slot) pairs a routed block
out["sdar_pair_scatters"] = pair_scatters(hlo, 131072)

# train-laguna-1chip's two flash calls at S 8,192 over 8 KV heads, as
# `blocks.attention` makes them: a window layer's, [1, 8192, 64, 128] under
# `SlidingWindow(512)` in its scope, and a full layer's, [1, 8192, 48, 128],
# causal; under the layer's remat policy as `window_moe` runs them, value
# and gradient: the three kernels each, how the scope shows in their names,
# and which of the cell's metrics would read each
laguna = window_moe.WindowMoeConfig()
laguna_queries = {}
for name in ("swa_flash_fwd_roofline", "swa_flash_bwd_roofline",
             "swa_attention_time_share", "laguna_full_attention_time_share"):
    with open(os.path.join(os.environ["REPO_ROOT"], "benchmarks", "metrics",
                           name + ".json")) as f:
        laguna_queries[name] = re.compile(json.load(f)["trace_query"]["op"])
for cell_call, n_heads, window in (
        ("laguna_window", 64, SlidingWindow(512)), ("laguna_full", 48, None)):
    attend = blocks.checkpointed(
        lambda q, k, v, window=window: blocks.attention(
            q, k, v, laguna, None, window), laguna)
    laguna_call = jax.value_and_grad(
        lambda q, k, v: attend(q, k, v).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))
    laguna_shapes = (spec((1, 8192, n_heads, 128), bf16),
                     spec((1, 8192, 8, 128), bf16),
                     spec((1, 8192, 8, 128), bf16))
    # the call as traced, its three kernels' bodies in it: no path, no
    # line; less the addresses of the functions its parameters name
    laguna_jaxpr = re.sub(r" at 0x[0-9a-f]+", "", str(jax.make_jaxpr(
        laguna_call)(*laguna_shapes)))
    out[cell_call + "_jaxpr"] = hashlib.sha256(
        laguna_jaxpr.encode()).hexdigest()
    # an unrolled plan's branches, one a `pl.when`, over the three kernels
    out[cell_call + "_branches"] = len(re.findall(r"\bcond\[", laguna_jaxpr))
    out[cell_call + "_band_steps"] = [
        plan.steps_band for plan in block_schedule(
            8192, 8192, 512, 512, window or True).values()]
    out[cell_call + "_triangle_steps"] = [
        plan.steps_triangle for plan in block_schedule(
            8192, 8192, 512, 512, window or True).values()]
    try:
        laguna_hlo = jax.jit(laguna_call).lower(
            *laguna_shapes).compile().as_text()
    except Exception as e:  # noqa: BLE001 - a refusal is the finding
        out[cell_call] = str(e)[:300]
        continue
    laguna_calls = [ln.strip() for ln in laguna_hlo.splitlines()
                    if 'custom_call_target="tpu_custom_call"' in ln]
    out[cell_call] = sorted(re.sub(r"\{[^}]*\}", "", re.match(
        r"%[\w.\-]+ = (.*?) custom-call\(", ln)[1]) for ln in laguna_calls)
    out[cell_call + "_scoped"] = ["swa.attend" in ln.split(" = ")[0]
                                  for ln in laguna_calls]
    out[cell_call + "_operands"] = flash_operands(laguna_hlo)
    out[cell_call + "_repeats"] = repeats(laguna_hlo, n_heads * 8192 * 128)
    out[cell_call + "_read_by"] = {
        metric: sum(1 for ln in laguna_calls if query.search(ln))
        for metric, query in laguna_queries.items()}

    # a call the default VMEM holds states no limit of its own
    out[cell_call + "_vmem_limits"] = re.findall(
        r"vmem_limit_bytes=(\d+)", laguna_jaxpr)

# train-smallthinker-1chip's two flash calls at S 16,384, [1, 16384, 28, 128]
# over 4 KV heads (a group of 7), under `SlidingWindow(4096)` and causal,
# value and gradient: the three kernels each, and the VMEM limit each states
for cell_call, window in (("smallthinker_window", SlidingWindow(4096)),
                          ("smallthinker_full", None)):
    long_call = jax.value_and_grad(
        lambda q, k, v, window=window: flash_attention(
            q, k, v, use_pallas=True, mask=window).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))
    long_shapes = (spec((1, 16384, 28, 128), bf16),
                   spec((1, 16384, 4, 128), bf16),
                   spec((1, 16384, 4, 128), bf16))
    long_jaxpr = re.sub(r" at 0x[0-9a-f]+", "", str(jax.make_jaxpr(
        long_call)(*long_shapes)))
    out[cell_call + "_jaxpr"] = hashlib.sha256(long_jaxpr.encode()).hexdigest()
    out[cell_call + "_vmem_limits"] = re.findall(
        r"vmem_limit_bytes=(\d+)", long_jaxpr)
    out[cell_call + "_plans"] = [
        [plan.static, len(plan.tiles), max(map(len, plan.rows)),
         plan.steps_triangle]
        for plan in block_schedule(16384, 16384, 512, 512,
                                   window or True).values()]
    try:
        long_hlo = jax.jit(long_call).lower(*long_shapes).compile().as_text()
    except Exception as e:  # noqa: BLE001 - a refusal is the finding
        out[cell_call] = str(e)[:300]
        continue
    out[cell_call] = "compiled"
    out[cell_call + "_operands"] = flash_operands(long_hlo)
    out[cell_call + "_repeats"] = repeats(long_hlo, 28 * 16384 * 128)

# the four cells' calls whose plans are LOOPS: the steps a kernel's plan runs
# in its loop's bodies, of all its steps; and the two not compiled above,
# value and gradient: Granite's at a 64-wide head and EvaByte's flash call
# under its rule, over [2,048 summaries ; 32,768 bytes]
for cell_call, rule, q_shape, keys, kv, scale in (
        ("laguna_full", True, (1, 8192, 48, 128), 8192, 8, None),
        ("smallthinker_full", True, (1, 16384, 28, 128), 16384, 4, None),
        ("granite", True, (1, 32768, 32, 64), 32768, 8, 1 / 64),
        ("evabyte", EvaWindows(32768, 2048, 16), (1, 32768, 32, 128), 34816,
         32, None)):
    out[cell_call + "_loop_bodies"] = [
        [plan.static, list(plan.body), plan.steps_loop_body, len(plan.tiles)]
        for plan in block_schedule(q_shape[1], keys, 512, 512, rule).values()]
    if cell_call in out:
        continue
    loop_call = jax.value_and_grad(
        lambda q, k, v, rule=rule, scale=scale: flash_attention(
            q, k, v, use_pallas=True, causal=rule, scale=scale)
        .astype(jnp.float32).sum(), argnums=(0, 1, 2))
    kv_shape = (1, keys, kv, q_shape[3])
    try:
        jax.jit(loop_call).lower(spec(q_shape, bf16), spec(kv_shape, bf16),
                                 spec(kv_shape, bf16)).compile()
        out[cell_call] = "compiled"
    except Exception as e:  # noqa: BLE001 - a refusal is the finding
        out[cell_call] = str(e)[:300]

# ONE checkpointed attention layer of `nemotron_h` (train-nemotron3-1chip:
# GQA 32 / 2 x 128, no RoPE, B 2 x S 2048) under `bodies`' policy, value
# and gradient: the Pallas calls the v5e's compiler leaves in it
attn_cfg = nemotron_h.NemotronHConfig(vocab_size=8, layers=(25,), mtp_depth=0)
attn_p = jax.eval_shape(lambda: nemotron_h.init(
    attn_cfg, jax.random.PRNGKey(0)))["one"]["attn"]
attn = nemotron_h.bodies(
    attn_cfg, jnp.broadcast_to(jnp.arange(2048), (2, 2048)), None, None)["attn"]
out["nemotron_attn_calls"] = [
    re.match(r"%[\w.\-]+ = (.*?) custom-call\(", ln.strip())[1]
    for ln in jax.jit(jax.value_and_grad(
        lambda x, p: attn(x, p)[0].astype(jnp.float32).sum(),
        argnums=(0, 1))).lower(
            spec((2, 2048, attn_cfg.d_model), bf16),
            on_chip(jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), attn_p))
        ).compile().as_text().splitlines()
    if 'custom_call_target="tpu_custom_call"' in ln]

# a share's row moves in that layer (`ops/row_moves.py`): what is `select`ed
# or gathered at a capacity's rows, and how each gather of a row tile of
# 4,096 is reached from the entry
caps = moe.share_capacities(16384, 8, 16, 128)


def rows_of(ln):
    # the rows of an instruction whose output is bf16[rows, 2048], or None
    m = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = bf16\[(\d+),2048\]", ln)
    return m and int(m[1])


out["sdar_caps"] = list(caps)
out["sdar_fill_selects"] = [
    ln[:120] for ln in ops
    if rows_of(ln) in caps and " select(" in ln]
computations = computations_of(hlo)
ways = called_as(computations)
out["sdar_row_gathers"] = sorted(
    (rows_of(ln), sorted(ways(name)))
    for name, lines in computations.items() for ln in lines
    if " gather(" in ln and rows_of(ln) not in (None, 16384))  # the embedding's
# the loops' two Pallas calls, by the names `ops/row_moves.py` gives them
out["sdar_row_move_calls"] = {
    name: sorted((rows_of(ln), sorted(ways(comp)))
                 for comp, lines in computations.items() for ln in lines
                 if rows_of(ln) and "tpu_custom_call" in ln
                 and re.match(r"\s*(ROOT )?%" + name + r"[\w.]* = ", ln))
    for name in ("unwritten", "row_tile")}
out["sdar_buffer_copies"] = [
    ln[:120] for ln in ops if rows_of(ln) in caps and re.search(
        r"\S* (copy|dynamic-update-slice|broadcast)\(", ln)]
out["sdar_conditionals"] = [
    len(re.search(r"branch_computations=\{([^}]*)\}", ln)[1].split(","))
    for ln in ops if re.search(r" conditional\(", ln)]


def folded_denominators(hlo):
    # `route`'s sum over k of its weights folded into their masked sum over
    # the experts: ONE reduce of the [T, k, E] select over both axes
    return [ln.strip()[:120] for ln in hlo.splitlines() if re.search(
        r"= f32\[16384\]\S* reduce\(.*dimensions=\{1,2\}", ln)]


def route_weights(x, w):
    return moe.route(x, w, 8, True).weights


# what `moe._formed_first` is there for: absent from the objective, absent
# from `route` alone at the cell's shapes, and there again with the barrier
# taken out (a compiler that stops folding leaves the barrier dead weight)
route_args = (spec((16384, 2048), bf16), spec((2048, 128), bf16))
out["sdar_folded_denominators"] = folded_denominators(hlo)
out["route_folded_denominators"] = folded_denominators(
    jax.jit(route_weights).lower(*route_args).compile().as_text())
formed_first, moe._formed_first = moe._formed_first, lambda weights: weights
out["route_folded_denominators_without_the_barrier"] = folded_denominators(
    jax.jit(lambda x, w: route_weights(x, w)).lower(
        *route_args).compile().as_text())
moe._formed_first = formed_first
for name in ("bd_flash_fwd_roofline", "bd_flash_bwd_roofline",
             "bd_attention_time_share"):
    with open(os.path.join(os.environ["REPO_ROOT"], "benchmarks", "metrics",
                           name + ".json")) as f:
        query = re.compile(json.load(f)["trace_query"]["op"])
    hits = [ln for ln in ops if query.search(ln)]
    out[name + "_events"] = len(hits)
    out[name + "_named"] = all(ln.startswith("%bd.attend") for ln in hits)
# a share's combine below the last capacity (T x k = 131,072 rows, whose
# own buffers are that long): nothing there is as long as the slots
out["sdar_slot_rows"] = [
    ln[:160] for ln in ops if re.search(r"branch_[01]_fun", ln)
    and re.search(r"= \(?bf16\[131072,2048\]", ln)]
out["sdar_branches"] = sorted(set(re.findall(r"branch_\d_fun", hlo)))
with open(os.path.join(os.environ["REPO_ROOT"], "benchmarks", "metrics",
                       "moe_combine_time_share.json")) as f:
    query = re.compile(json.load(f)["trace_query"]["op"])
hits = [ln for ln in ops if query.search(ln)]
kernels = [ln for ln in hits if "tpu_custom_call" in ln]
out["moe_combine_kernels"] = len(kernels)
out["moe_combine_kernels_are_the_sums"] = all(
    re.match(r"%tgmm[\w.]* = bf16\[64,256,2048\]", ln) for ln in kernels)
out["moe_combine_other_hits_below_the_last_capacity"] = [
    ln[:160] for ln in hits if "tpu_custom_call" not in ln
    and re.search(r"branch_0_fun", ln)]

# Mamba-2's chunked scan (train-nemotron3-1chip): the Pallas forward and
# the two backward kernels at x [2, 2048, 128, 64], state 128, 8 groups,
# and their events as the trace will name them
from ray_tpu.ops import ssd as ssd_op
ssd_args = (spec((2, 2048, 128, 64), bf16),
            spec((2, 2048, 128), jnp.float32),
            spec((2, 2048, 128), jnp.float32),
            spec((2, 2048, 8, 128), bf16), spec((2, 2048, 8, 128), bf16))
hlo = jax.jit(jax.value_and_grad(  # the value: or the forward is dead code
    lambda *a: ssd_op.ssd_scan(*a, use_pallas=True)[0].astype(
        jnp.float32).sum(), argnums=(0, 1, 2, 3, 4))).lower(
            *ssd_args).compile().as_text()
out["ssd_calls"] = [
    re.sub(r"custom-call\(.*", 'custom-call(%a), custom_call_target='
           '"tpu_custom_call"', ln.strip())
    for ln in hlo.splitlines() if "tpu_custom_call" in ln and " = " in ln]

cfg = llama.LlamaConfig.small_1b()
params = jax.eval_shape(lambda: llama.init(cfg, jax.random.PRNGKey(0)))
eng = PagedInferenceEngine(params, cfg, max_batch=8, max_len=1024,
                           block_size=64)
b, i32 = eng.max_batch, jnp.int32
eng._decode.lower(
    on_chip(params), on_chip(eng.pool), spec((b, 1), i32),
    spec((b, eng.max_blocks_per_seq), i32), spec((b,), i32),
    spec((b,), i32), spec((b,), jnp.bool_), spec((2,), jnp.uint32),
    spec((), i32), spec((), i32), max_steps=16).compile()
out["paged_decode"] = "compiled"
print("RESULT " + json.dumps(out))
"""

# A process of its own, so that the compiler's dump (`--xla_mosaic_dump_to`,
# which libtpu reads once, at start-up) holds these three kernels only: the
# whole of `_SCRIPT` would leave 1.6 GB behind.
_KDA_SCRIPT = r"""
# the chunked delta rule (train-ling-1chip): the Pallas forward and the two
# backward kernels at [4, 32, 2048, 128], and their events as the trace
# will name them
import glob
from ray_tpu.ops import kda as kda_op
kda_args = (spec((4, 32, 2048, 128), bf16),) * 3 + (
    spec((4, 32, 2048, 128), jnp.float32), spec((4, 32, 2048), jnp.float32))
hlo = jax.jit(jax.value_and_grad(  # the value: or the forward is dead code
    lambda *a: kda_op.kda(*a, g_min=kda_op.G_MIN_BOUNDED,
                          use_pallas=True).astype(jnp.float32).sum(),
    argnums=(0, 1, 2, 3, 4))).lower(*kda_args).compile().as_text()
out["kda_calls"] = [
    re.sub(r"custom-call\(.*", 'custom-call(%a), custom_call_target='
           '"tpu_custom_call"', ln.strip())
    for ln in hlo.splitlines() if "tpu_custom_call" in ln and " = " in ln]
# a grid step's body is straight-line code, one `vdwg` an MXU pass
out["kda_rows_a_step"] = kda_op._specs(4 * 32, 32)[0]
for kernel in ("_fwd_kernel", "_states_kernel", "_bwd_kernel"):
    (path,) = glob.glob(os.path.join(
        os.environ["MOSAIC_DUMP"], f"*-{kernel}-post-finalize-llo.txt"))
    with open(path) as f:
        out["kda" + kernel + "_vdwg"] = f.read().count('"llo.vdwg"')
# q, k and v from their projections (`ops/kda_prep.py`): the forward and the
# backward call at train-ling-1chip's and train-solar2-1chip's shapes
from ray_tpu.ops import kda_prep
for cell, (b, h, s) in (("ling", (4, 32, 2048)), ("solar", (1, 64, 8192))):
    prep_args = ([spec((b, s, h * 128), bf16)] * 3,
                 [spec((4, h, 128), bf16)] * 3)
    prep_grads = jax.value_and_grad(lambda xs, taps: sum(
        t.astype(jnp.float32).sum() for t in kda_prep.prep(xs, taps, bf16)),
        argnums=(0, 1))
    assert kda_prep.fused((b, s, h, 128), prep_args[1][0]) == (
        jax.default_backend() == "tpu")
    out["kda_prep_vmem_limits_" + cell] = re.findall(
        r"vmem_limit_bytes=(\d+)", str(jax.make_jaxpr(prep_grads)(*prep_args)))
    hlo = jax.jit(prep_grads).lower(*prep_args).compile().as_text()
    calls_of = lambda hlo: [  # noqa: E731
        re.sub(r"custom-call\(.*", 'custom-call(%a), custom_call_target='
               '"tpu_custom_call"', ln.strip())
        for ln in hlo.splitlines() if "tpu_custom_call" in ln and " = " in ln]
    out["kda_prep_calls_" + cell] = calls_of(hlo)
    # the decay gate under the cell's form: Ling's bounded sigmoid, Solar's
    # softplus; a cotangent that is no constant, or XLA folds the call away
    bound = {"ling": -5.0, "solar": None}[cell]
    gate_args = (spec((b, s, h * 128), bf16), spec((h, 128), jnp.float32),
                 spec((h,), jnp.float32), spec((b, h, s, 128), jnp.float32))
    gate_grads = jax.value_and_grad(lambda a, bias, a_log, w: (
        kda_prep.gate(a, bias, a_log, bound) * w).sum(), argnums=(0, 1, 2))
    out["kda_prep_vmem_limits_" + cell] += re.findall(
        r"vmem_limit_bytes=(\d+)", str(jax.make_jaxpr(gate_grads)(*gate_args)))
    out["kda_gate_calls_" + cell] = calls_of(
        jax.jit(gate_grads).lower(*gate_args).compile().as_text())
print("RESULT " + json.dumps(out))
"""


def _run(script, **more):
    env = dict(os.environ, JAX_PLATFORMS="cpu", REPO_ROOT=REPO_ROOT,
               PYTHONPATH=REPO_ROOT + os.pathsep
               + os.environ.get("PYTHONPATH", ""), **more)
    proc = subprocess.run([sys.executable, "-c", _HEAD + script], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    (line,) = [ln for ln in proc.stdout.splitlines()
               if ln.startswith("RESULT ")]
    return json.loads(line[len("RESULT "):])


@pytest.fixture(scope="module")
def compiled(tmp_path_factory):
    """One after the other: a process keeps libtpu until it exits."""
    out = _run(_SCRIPT)
    dump = str(tmp_path_factory.mktemp("mosaic"))
    out.update(_run(_KDA_SCRIPT, MOSAIC_DUMP=dump, LIBTPU_INIT_ARGS=" ".join(
        [os.environ.get("LIBTPU_INIT_ARGS", ""),
         "--xla_mosaic_dump_to=" + dump]).strip()))
    return out


def test_flash_calls_with_triangle_steps_compile_for_v5e(compiled):
    """The cells' unrolled causal and block-causal calls since PR 51 runs a
    tile the rule cuts along its sub-tile diagonal in two halves, on 12 of
    its 16 sub-tiles: causal `[4, 32, 2048, 128]` over 8 KV heads (4 of a kernel's 10
    steps), the call in parts at `[4, 32, 2048, 128 + 64]` (the same plan)
    and `BlockDiffusion(2048, 4)` at `[4, 32, 4096, 128]` over 4 KV heads
    (8 of 24), all three kernels of each, under the default VMEM limit."""
    assert compiled["flash_s2048_plans"] == {
        name: [True, 6, 4, 4] for name in ("fwd", "dq", "dkv")}
    assert compiled["flash_bd_plans"] == {
        name: [True, 12, 12, 4, 8] for name in ("fwd", "dq", "dkv")}
    for call in ("flash_s2048", "flash_mla", "flash_bd"):
        assert compiled[call] == "compiled", call
    assert len(compiled["mla_parts_calls"]) == 3
    assert compiled["flash_s2048_vmem_limits"] == [] \
        == compiled["flash_bd_vmem_limits"]


def test_the_dense_cells_flash_call_lowers_to_the_text_it_had(compiled):
    """train-1chip's call, `[4, 2048, 32, 128]` causal over 8 KV heads, value
    and gradient, as LOWERED for the v5e, every kernel's assembly in it less
    its locations (`tools/step_lowering_hash.py`): PR 63's text, whose
    backward kernels take lse and delta lane-dense (`_stat_forms`) and whose
    delta is a reduce with no `keepdims` (until then PR 59's: its plans are
    unrolled, 10 steps a kernel, and no change to a loop plan reached it)."""
    assert compiled["flash_s2048_lowered"] == (
        "61fca4b1df28f3d83db4233f1a802ca6"
        "5a28498423421353c6c4e59c4fa8e15c")


@pytest.mark.parametrize("call, steps, in_bodies", [
    ("laguna_full", 136, 112), ("smallthinker_full", 528, 480),
    ("granite", 2080, 1984), ("evabyte", 304, 160)])
def test_loop_plans_run_their_whole_tiles_in_bodies_for_v5e(
        compiled, call, steps, in_bodies):
    """The four cells' flash calls whose plans are loops, as the v5e's
    compiler takes them since a row runs its whole tiles four and then two a
    body of straight-line code with no mask (`_LOOP_BODY`): `CAUSAL` at `[1,
    48, 8192, 128]` over 8 KV heads, at `[1, 28, 16384, 128]` over 4, at
    `[1, 32, 32768, 64]` over 8 (the call the compiler once refused by 764
    KiB of VMEM: `_in_vmem`), and `EvaWindows(32768, 2048, 16)` at `[1, 32,
    32768, 128]` over 34,816 keys: forward, dq and dk/dv each compile, under
    the limits the calls stated before (`_vmem_limit`: the bodies' score
    tiles are the compiler's to place)."""
    # a refusal is a string that says why; Laguna's call, compiled under its
    # layer's policy, is its kernels' signatures
    assert compiled[call] == "compiled" or isinstance(compiled[call], list), \
        compiled[call]
    assert compiled[call + "_loop_bodies"] \
        == [[False, [4, 2], in_bodies, steps]] * 3


def test_flash_fwd_bwd_compiles_for_v5e(compiled):
    assert compiled["device_kind"] == "TPU v5 lite"
    # forward, dq and dk/dv kernels
    assert compiled["flash_custom_calls"] >= 3
    assert compiled["flash_s2048"] == "compiled"


def test_delta_rule_kernels_compile_for_v5e_under_their_own_signatures(
        compiled):
    """`ops/kda.py` at train-ling-1chip's shape: THREE Pallas calls (the
    forward, the backward pass's walk forwards and its walk backwards),
    each taken by the cell's KDA queries that are for it and by no flash
    or grouped-matmul query."""
    calls = compiled["kda_calls"]
    assert len(calls) == 3, calls
    query = lambda name: re.compile(json.load(open(os.path.join(  # noqa: E731
        REPO_ROOT, "benchmarks", "metrics", name + ".json")))[
            "trace_query"]["op"])
    took = lambda name: [bool(query(name).search(c)) for c in calls]  # noqa: E731
    assert sorted(took("kda_fwd_roofline")) == [False, False, True]
    assert sorted(took("kda_bwd_roofline")) == [False, True, True]
    assert [a or b for a, b in zip(took("kda_fwd_roofline"),
                                   took("kda_bwd_roofline"))] == [True] * 3
    assert took("kda_time_share") == [True] * 3
    for other in ("mla_flash_fwd_roofline", "mla_flash_bwd_roofline",
                  "bd_attention_time_share", "flash_fwd_roofline",
                  "flash_bwd_roofline", "moe_gmm_roofline"):
        assert took(other) == [False] * 3, other
    # From the compiler's own dump (`*-post-finalize-llo.txt`): the MXU
    # passes a (batch, head) row costs a chunk. 76 in the forward and 74 in
    # the first walk while every row's solve ran its own ten 64 x 64 float32
    # `highest` products (60 passes); 46 and 44 with two rows to a pass
    # (PERF.md section 6, PR 40). A later edit that unpacks the solve in
    # silence fails here, not only in a benchmark. The second walk runs no
    # solve: 39.
    rows = compiled["kda_rows_a_step"]
    assert rows % 2 == 0
    assert compiled["kda_fwd_kernel_vdwg"] / rows < 60
    assert compiled["kda_states_kernel_vdwg"] / rows < 60
    assert compiled["kda_bwd_kernel_vdwg"] / rows < 60


@pytest.mark.parametrize("cell, b, h, s", [
    ("ling", 4, 32, 2048), ("solar", 1, 64, 8192)])
def test_the_delta_rules_operands_compile_for_v5e_under_no_other_signature(
        compiled, cell, b, h, s):
    """`ops/kda_prep.py` at train-ling-1chip's and train-solar2-1chip's
    shapes: FOUR Pallas calls: q, k and v heads first out of `prep`'s
    forward, three dx and the taps' float32 gradient out of its backward; g
    float32 heads first out of `gate`'s forward (Ling's bounded form, Solar's
    softplus), the projection's cotangent and a head's two sums over the
    tokens out of its backward; none stating a VMEM limit (the v5e's compiler
    places them in the 16 MiB a call gets unasked), all named by their
    scope, which is how `kda_prep_time_share` takes them; and NO other
    metric's query that names `tpu_custom_call` takes any: a flash backward
    is one or two bf16 four-dim outputs, a KDA forward `(bf16[3-dim],
    f32[3-dim])`, the SSD's first walk one `f32[3-dim]`."""
    import glob

    calls = compiled["kda_prep_calls_" + cell] \
        + compiled["kda_gate_calls_" + cell]
    assert compiled["kda_prep_vmem_limits_" + cell] == []
    assert len(calls) == 4 and all(c.startswith("%kda.prep") for c in calls)
    outputs = sorted(re.findall(r"\w+\[[\d,]*\]", c.split(" custom-call")[0])
                     for c in calls)
    assert outputs == sorted([
        [f"bf16[{b},{h},{s},128]"] * 3,
        [f"bf16[{b},{s},{h * 128}]"] * 3 + [f"f32[{b},12,{h * 128}]"],
        [f"f32[{b},{h},{s},128]"],
        [f"bf16[{b},{s},{h * 128}]", f"f32[{b},{h},2,128]"]])
    for path in sorted(glob.glob(os.path.join(
            REPO_ROOT, "benchmarks", "metrics", "*.json"))):
        with open(path) as f:
            query = json.load(f).get("trace_query", {}).get("op", "")
        if "tpu_custom_call" not in query:
            continue
        name = os.path.basename(path)[:-len(".json")]
        took = [bool(re.search(query, c)) for c in calls]
        assert took == [name == "kda_prep_time_share"] * 4, name


def test_state_space_kernels_compile_for_v5e_under_their_own_signatures(
        compiled):
    """`ops/ssd.py` at train-nemotron3-1chip's shape: THREE Pallas calls (the
    forward, the backward pass's walk forwards and its walk backwards),
    each taken by the cell's SSD queries that are for it and by no flash
    or grouped-matmul query the cell is listed under."""
    calls = compiled["ssd_calls"]
    assert len(calls) == 3, calls
    query = lambda name: re.compile(json.load(open(os.path.join(  # noqa: E731
        REPO_ROOT, "benchmarks", "metrics", name + ".json")))[
            "trace_query"]["op"])
    took = lambda name: [bool(query(name).search(c)) for c in calls]  # noqa: E731
    assert sorted(took("ssd_fwd_roofline")) == [False, False, True]
    assert sorted(took("ssd_bwd_roofline")) == [False, True, True]
    assert [a or b for a, b in zip(took("ssd_fwd_roofline"),
                                   took("ssd_bwd_roofline"))] == [True] * 3
    assert took("ssd_time_share") == [True] * 3
    for other in ("flash_fwd_roofline", "flash_bwd_roofline",
                  "moe_gmm_roofline", "nemotron_moe_held_time_share",
                  "mla_flash_fwd_roofline", "mla_flash_bwd_roofline",
                  "bd_attention_time_share"):
        assert took(other) == [False] * 3, other


def test_paged_decode_compiles_for_v5e(compiled):
    assert compiled["paged_decode"] == "compiled"


@pytest.mark.parametrize("seq", ["flash_s3584", "flash_s4096", "flash_s8192"])
def test_flash_backward_compiles_at_long_sequences(compiled, seq):
    """The limit, pinned (it was "refused at S 8192", for VMEM, until
    PR 26): if this flips back, update the docstring of
    ops.flash_attention.flash_attention with it. dk/dv is unrolled at
    S 3,584 (28 steps a head: its budget) and a loop over its table at
    S 4,096 (36) and S 8,192 (136), as before PR 38."""
    assert compiled[seq] == "compiled", compiled[seq]
    assert compiled["flash_causal_dkv_static"] == {
        "2048": True, "3584": True, "4096": False, "8192": False}


def test_flash_with_keys_wider_than_values_compiles_for_v5e(compiled):
    """MLA's call at train-joyai-1chip's shape, q and k [4, 2048, 32, 192],
    v [4, 2048, 32, 128]: forward, dq and dk/dv. Mosaic takes the 192-wide
    blocks as they are (a block's last dim may be the array's)."""
    assert compiled["flash_mla_custom_calls"] >= 3
    assert compiled["flash_mla"] == "compiled"


def test_latent_attention_in_parts_as_compiled_for_v5e(compiled):
    """One checkpointed `mixers.mla_sublayer`, value and gradient, at
    train-joyai-1chip's widths: the flash call takes q `[4, 32, 2048, 128]`,
    the rotary q `[.., 64]`, k, ONE rotary key `[4, 1, 2048, 64]` and v, and
    its three kernels keep the output signatures that
    `benchmarks/metrics/mla_flash_{fwd,bwd}_roofline.json` match in the
    trace (the forward once; dq and dk/dv, two events a call). THREE Pallas
    calls, not four: the layer's policy saves the forward's `o` and `lse`,
    so the backward pass does not run it again. And nothing but the dq and
    dk kernels writes a `[4, 32, 2048, 192]` array: no q or k is
    concatenated to 192 channels, the rotary key is not copied to 32 heads,
    in the forward pass, its recomputation or the backward pass."""
    wide, narrow = "bf16[4,32,2048,192]", "bf16[4,32,2048,128]"
    calls = [re.sub(r"\{[^}]*\}", "", c) for c in compiled["mla_parts_calls"]]
    assert sorted(calls) == sorted([
        f"({narrow}, f32[4,32,2048,1])", wide, f"({wide}, {narrow})"])
    assert compiled["mla_flash_fwd_roofline_events"] == 1
    assert compiled["mla_flash_bwd_roofline_events"] == 2
    assert compiled["mla_parts_wide_ops"] == []


def test_nemotron_attention_layer_saves_the_flash_residuals_for_v5e(compiled):
    """One checkpointed attention layer of `nemotron_h` (GQA 32 / 2, the
    whole-q form of the call) at train-nemotron3-1chip's widths and batch,
    value and gradient. `_bodies` asks its policy to save the flash call's
    `o` and `lse` by the names the ONE forward rule gives them, so, as in
    the latent-attention layer above: THREE Pallas calls, not four, under
    the output signatures `flash_fwd_roofline` and `flash_bwd_roofline`
    read."""
    out = "bf16[2,32,2048,128]"
    calls = [re.sub(r"\{[^}]*\}", "", c)
             for c in compiled["nemotron_attn_calls"]]
    assert sorted(calls) == sorted([
        f"({out}, f32[2,32,2048,1])", out, f"({out}, {out})"])


@pytest.mark.parametrize("shape", ["gmm_up", "gmm_down"])
def test_grouped_matmul_fwd_bwd_compiles_for_v5e(compiled, shape):
    """The MoE dispatch's Pallas grouped matmuls at train-olmoe-1chip's
    shapes with `ops/grouped_matmul.py`'s tilings: forward, the rows'
    gradient and the weights' gradient."""
    assert compiled[shape + "_custom_calls"] == 3
    assert compiled[shape] == "compiled"


def test_tp_boundary_is_not_an_all_reduce_for_v5e_2x2(compiled):
    """train-4chip's step over fsdp 2 x tp 2, as the v5e's compiler leaves
    it: with the residual stream sequence-sharded over tp between
    sublayers no `[8, 2048, 4096]` all-reduce stands at the top level of a
    layer loop (four did, exposed: PERF.md §6, PR 30). Attention's two sums
    over tp (after wo; the dx of q/k/v) are `all-reduce-scatter` fusions
    with half the rows out; the MLP's transfers are the five
    collective-permutes of `blocks.mlp_ring` (forward: the rows in, the
    sums out; backward: the rows again for the recomputation, and the two
    transposes); v is projected with heads over tp like q and k, so nothing
    is turned round by an all-to-all."""
    assert compiled["tp_loops"]
    assert compiled["tp_all_reduces_in_loops"] == []
    assert compiled["tp_reduce_scatters_in_loops"] >= 2
    assert compiled["tp_permutes_in_loops"] == 5
    assert compiled["tp_all_to_alls"] == 0


def test_the_head_forms_its_gradient_with_its_logits_for_v5e(compiled):
    """`blocks.chunked_ce`, value and gradient, as the v5e's compiler leaves
    it. Alone at train-smallthinker-1chip's widths its loop holds THREE
    products of 1,024 x 2,560 x 37,984, the logits, d hidden and d lm_head
    (four until PR 53: the backward pass formed the logits again). In
    train-4chip's step over fsdp 2 x tp 2 it is ONE loop, and that loop holds
    no more collectives than the two loops it replaces held between them
    (11: in each, lm_head gathered over fsdp, the chunk's rows over the
    batch and the softmax's sums over tp; d hidden's sum over tp in the
    backward one): 6, a forward's and d hidden's."""
    assert compiled["head_loop_products"] == [
        "bf16[1024,2560]", "bf16[1024,37984]", "bf16[2560,37984]"]
    assert compiled["tp_head_loops"] == 1
    assert 0 < compiled["tp_head_loop_collectives"] <= 11


def test_the_embeddings_gradient_is_a_sorted_sum_as_compiled_for_v5e(compiled):
    """`blocks.embed_rows`' gradient by the table at
    train-smallthinker-1chip's shape (16,384 rows of 2,560 into 37,984), as
    the v5e's compiler leaves it. With the sorted sum: ONE sort of the ids,
    ONE gather of the 16,384 rows into token order, ONE Pallas call, `tgmm`,
    and no scatter whose operand or result is `[37984, 2560]`. The form it
    replaces on one chip, and which stands everywhere else, holds that
    scatter and no kernel; it is the parent's text:

        ROOT %scatter-add.1 = bf16[37984,2560] scatter(%param_0.2,
            %transpose.6, %transpose.7), update_window_dims={1}, ...
        ROOT %fusion = bf16[37984,2560] fusion(%get-tuple-element,
            %constant.3, %convert_bitcast_fusion, %broadcast_clamp_fusion),
            kind=kCustom, calls=%fused_computation.2

    (`fusion_bf16_37984_2560` in the cell's trace: 17.8 ms a step; XLA sorts
    the ids and gathers the updates itself, then adds row after row). At
    train-1chip's shape, rows of 4,096, a power of two wide, that adding is
    fast and the scatter-add stands on the chip as well.

    train-4chip's step over fsdp 2 x tp 2 keeps the scatter-add, into the
    whole `[32768, 4096]` table, and gathers the table once, for the forward
    lookup, as the parent does: its optimised text less the instructions'
    numbers is the parent's, line for line (PR 56: 4,375 lines)."""
    assert compiled["embed_grad_sorted"] == {
        "table_scatters": 0, "kernels": ["tgmm"], "row_gathers": 1,
        "sorts": 1}
    for kept in ("embed_grad_scatter", "embed_grad_whole_lanes"):
        assert compiled[kept]["table_scatters"] == 1
        assert compiled[kept]["kernels"] == []
    assert compiled["tp_table_scatters"] == 1
    assert compiled["tp_table_all_gathers"] == 1
    assert compiled["tp_sum_kernels"] == 0


def test_moe_layer_backward_as_compiled_for_v5e(compiled):
    """The gradient of one `moe_layer` under remat "dots" at
    train-olmoe-1chip's shapes, `router_losses`' two terms in the loss as
    the cell's step has them, as the v5e's compiler leaves it: 11 Pallas
    calls (3 forward, gate and up recomputed, 6 backward; 12 when the top-k
    weights were applied after the down projection and the recomputation
    reran it) and 5 gathers of [65536, 2048] rows (6 then)."""
    assert compiled["moe_layer_custom_calls"] == 11
    assert compiled["moe_layer_row_gathers"] == 5


def test_router_as_compiled_for_v5e_scatters_no_pair(compiled):
    """That gradient and train-sdar-1chip's compiled objective: no scatter
    reads or writes an array of the T x k (token, slot) pairs, so no gather
    is left to autodiff to transpose and nothing is counted by a scatter.
    Two `bincount`s of 65,536 pairs a forward (0.57 ms each on the v5e;
    1.15 ms for SDAR's 131,072) and the transpose of `top_k`'s values
    (0.44 ms) stood here until PR 44, unseen while this file compiled the
    layer without its loss terms: the counts were dead code (PERF.md
    section 6). What is left scatters a few hundred scalars of the grouped
    matmuls' plans."""
    assert compiled["moe_layer_pair_scatters"] == []
    assert compiled["moe_layer_counts"] >= 1
    assert compiled["sdar_pair_scatters"] == []


def test_route_denominators_as_compiled_for_v5e(compiled):
    """`moe._formed_first` and the fold it exists for, tied together. Left
    to itself the v5e's compiler makes ONE reduce over `[16384, 8 x 128]`
    of `route`'s masked sum over the experts and `norm_topk_prob`'s sum over
    k, the denominators as a second output: 0.417 ms a call on the chip
    where the two reduces take 0.09 (10 ms of train-sdar-1chip's step,
    PERF.md section 6, PR 44), in another order of summation than `top_k`'s
    values had. With the barrier no such reduce is in `route` alone nor in
    the cell's compiled objective. Without it the fold is back: the day a
    compiler stops folding, the last assertion fails and the barrier goes;
    the day one folds through the barrier, the first two do."""
    assert compiled["sdar_folded_denominators"] == []
    assert compiled["route_folded_denominators"] == []
    assert len(compiled["route_folded_denominators_without_the_barrier"]) == 1


def test_flash_under_the_block_diffusion_rule_compiles_for_v5e(compiled):
    """train-sdar-1chip's call, q `[4, 4096, 32, 128]` over [x_t ; x_0] with
    4 kv heads under `BlockDiffusion(2048, 4)`: forward, dq and dk/dv (24
    steps a head in all: unrolled too since PR 38), each with its four x_t
    diagonal tiles as diagonal steps and, since PR 51, the four x_0 and the
    four x_t -> x_0 diagonal tiles as triangle steps (12 of their 16 sub-
    tiles), compile for the v5e under the default VMEM limit as the three
    Pallas calls they were, and no [4096, 4096] score or mask array is in
    the compiled program."""
    assert compiled["flash_bd_custom_calls"] == 3
    assert compiled["flash_bd_plans"] == {
        name: [True, 12, 12, 4, 8] for name in ("fwd", "dq", "dkv")}
    assert compiled["flash_bd"] == "compiled"
    assert compiled["flash_bd_dense"] == []


def test_window_and_full_flash_calls_at_s8192_compile_for_v5e(compiled):
    """train-laguna-1chip's two calls, value and gradient, as the v5e's
    compiler takes them: the window layer's three kernels at `[1, 64, 8192,
    128]` (all unrolled at ONE step a row: 15 band steps, each a batch of 4
    groups x [128, 640] scores, and the first row's own tile (dk/dv: the
    last's); the fifteen are one kind of band, a tile before the row's own
    block or with it, and share ONE branch, so a kernel has two; before PR
    48 two whole tiles a row, 16 branches, and dk/dv a loop over 31) and
    the full layer's at
    `[1, 48, 8192, 128]` (`CAUSAL`, loops of up to 16 and of 136: no band
    step), K and V of the whole sequence in VMEM. The window calls
    carry their scope in their names (`%swa.attend.3`; the forward of a
    layer outside a scan `%jvp_swa.attend_.1`), which is how the cell's four
    attention metrics tell them from the full ones in one trace."""
    out = "bf16[1,{},8192,128]".format
    for name, heads in (("laguna_window", 64), ("laguna_full", 48)):
        assert compiled[name] == sorted([
            f"({out(heads)}, f32[1,{heads},8192,1])", out(heads),
            f"({out(heads)}, {out(heads)})"]), compiled[name]
    assert compiled["laguna_window_band_steps"] == [15] * 3
    assert compiled["laguna_window_branches"] == 3 * 2
    assert compiled["laguna_full_branches"] == 0   # loops
    assert compiled["laguna_full_band_steps"] == [0] * 3
    # both are GQA calls, 8 KV heads: since PR 49 neither traces a repeat
    # of K and V, so neither is the text it was (tests/test_ops.py pins the
    # calls with no shared KV head to theirs)
    assert compiled["laguna_full_jaxpr"] != (     # PR 48's
        "108a1a8a73455e8e4f31f9f24c5ff321021286d1f3ea41e5cf16d89d56f8eb18")
    assert compiled["laguna_window_jaxpr"] != (   # PR 47's
        "0192ab54ecd8b9eece3b74fe696ad7f626f15efc50d82530140f6db30591e62a")
    # and since PR 50, which has a longer call state its VMEM limit, both
    # traced to the text PR 49 left: at S 8,192 nothing is stated. The full
    # call's loops run their rows' whole tiles in bodies of 4 and of 2 steps
    # since PR 60: its text; the window call's first row, its own tile
    # alone, is a triangle step since PR 51 (dk/dv: the last row), the one
    # branch of its two that is not the band's. Both are PR 63's texts: the
    # backward kernels take lse and delta lane-dense (`_stat_forms`)
    assert compiled["laguna_full_jaxpr"] != (     # PR 49's, until PR 60
        "dc1bde509d1bcbdffd1972a302135611f48b337c4a9784192d6e2610f0bb16f6")
    assert compiled["laguna_full_jaxpr"] == (
        "4e4bcd7dbef7b4155315f33450f13c351181a53b24a248ee83cd352b8a4545e1")
    assert compiled["laguna_window_jaxpr"] != (   # PR 49's
        "bb8f601aafbc03b1d2e47ad5becb97d305d31ef91a6140603b735510530a66e6")
    assert compiled["laguna_window_jaxpr"] == (
        "3e5a242fb1c22c7bb2d45aaa30b475ca53420231789c93681f0c05f64a0cda83")
    assert compiled["laguna_window_triangle_steps"] == [1] * 3
    assert compiled["laguna_full_triangle_steps"] == [0] * 3
    assert compiled["laguna_full_vmem_limits"] == []
    assert compiled["laguna_window_vmem_limits"] == []
    assert compiled["laguna_window_scoped"] == [True] * 3
    assert compiled["laguna_full_scoped"] == [False] * 3
    assert compiled["laguna_window_read_by"] == {
        "swa_flash_fwd_roofline": 1, "swa_flash_bwd_roofline": 2,
        "swa_attention_time_share": 3, "laguna_full_attention_time_share": 0}
    assert compiled["laguna_full_read_by"] == {
        "swa_flash_fwd_roofline": 0, "swa_flash_bwd_roofline": 0,
        "swa_attention_time_share": 0, "laguna_full_attention_time_share": 3}


def test_window_and_full_flash_calls_at_s16384_compile_for_v5e(compiled):
    """train-smallthinker-1chip's two calls, value and gradient: `[1, 28,
    16384, 128]` over 4 KV heads under `SlidingWindow(4096)` (8 tiles of
    512: rows of 9 steps, 252 a (batch, head)) and `CAUSAL` (rows of up to
    32, 528), all six kernels loops (a row's whole tiles four and two a
    body, PR 60), the window's with one unrolled branch for their 24 interior
    rows beside it. One KV head's K and V whole are 2 x 4
    MiB, twice over in the pipeline's buffers: the compiler refused every
    one ("Scoped allocation with size 16.75M and limit 16.00M") until each
    stated its own limit, its blocks twice plus 16 MiB (`_vmem_limit`): 32.5
    to 33.3 MiB of the v5e's 128."""
    for name, steps, longest, digest in (
            ("smallthinker_window", 252, 9,
             "bef3892b91a1a233db39081179712960"
             "950db6c4171fc25de4112552eaf48fea"),
            ("smallthinker_full", 528, 32, "ae862653013c28790e66f0838c5a8616"
             "504f03a6c2e69e87db46623a205a1684")):
        assert compiled[name] == "compiled", compiled[name]
        # loops: no triangle step. The window call's 24 rows of ONE shape
        # share an unrolled branch (PR 58); the loops, all of the causal
        # call's rows and the window call's 8 edge rows, run a row's whole
        # tiles in bodies of 4 and of 2 steps with no mask (PR 60); PR 63's
        # texts: lse and delta reach the backward kernels lane-dense
        assert compiled[name + "_plans"] == [[False, steps, longest, 0]] * 3
        assert compiled[name + "_jaxpr"] == digest
        limits = sorted(map(int, compiled[name + "_vmem_limits"]))
        assert len(limits) == 3   # forward, dq, dk/dv
        assert 32 * 2**20 < limits[0] <= limits[-1] < 34 * 2**20, limits


# the cells' GQA calls: (query heads, KV heads, batch, sequence)
_GQA_CALLS = {
    "smallthinker_window": (28, 4, 1, 16384),
    "smallthinker_full": (28, 4, 1, 16384),
    "laguna_window": (64, 8, 1, 8192), "laguna_full": (48, 8, 1, 8192),
    "flash_bd": (32, 4, 4, 4096), "flash_s2048": (32, 8, 4, 2048)}


@pytest.mark.parametrize("call", sorted(_GQA_CALLS))
def test_gqa_flash_calls_read_k_and_v_at_the_kv_heads_count_for_v5e(
        compiled, call):
    """The cells' GQA calls, value and gradient, as compiled for the v5e:
    `[1, 28 / 4, 16384, 128]` under the window rule and causal, a group of 7
    (train-smallthinker-1chip), `[1, 64 / 8, 8192, 128]` under the window
    rule and `[1, 48 / 8, 8192, 128]` causal (train-laguna-1chip), `[4, 32 / 4, 4096, 128]` under the
    block-diffusion rule (train-sdar-1chip), `[4, 32 / 8, 2048, 128]`
    (the Mistral cells). Each of the three kernels takes K and V at the KV
    heads' count beside q (and dO) at the query heads', the index map
    `head // group` compiles, and nothing in the program copies an array
    to a K's or V's size at the query heads' count (before PR 49 two
    `broadcast`s did, `bf16[8192,8,8,128]` in the window call)."""
    h, h_kv, b, s = _GQA_CALLS[call]
    operands = compiled[call + "_operands"]
    assert len(operands) == 3
    shared, own = f"bf16[{b},{h_kv},{s},128]", f"bf16[{b},{h},{s},128]"
    for kernel in operands:
        assert kernel.count(shared) == 2, kernel     # K and V
        assert kernel.count(own) in (1, 2), kernel   # q; with dO
    assert compiled[call + "_repeats"] == []


def test_sdar_layer_as_compiled_for_v5e(compiled):
    """One layer of the cell's model and the whole objective at its widths
    and batch, value and gradient: the Pallas kernels are in it (the flash
    forward twice, the layer's remat reruns it; dq; dk/dv; the share's
    grouped matmuls), no dense [2L, 2L] array is, and the flash events
    carry the scope `bd.attend` and match the new metrics' queries."""
    assert compiled["sdar_custom_calls"] >= 4 + 3
    assert compiled["sdar_dense"] == []
    assert compiled["bd_flash_fwd_roofline_events"] == 2
    assert compiled["bd_flash_bwd_roofline_events"] == 2
    assert compiled["bd_attention_time_share_events"] == 4
    for name in ("bd_flash_fwd_roofline", "bd_flash_bwd_roofline"):
        assert compiled[name + "_named"], name


def test_a_share_combines_from_the_buffers_rows_as_compiled_for_v5e(compiled):
    """The same compiled layer: below the last capacity (T x k rows, whose
    own buffers are that long) no array has the 131,072 rows that a gather
    of every token's 8 slots writes, and `moe_combine_time_share`'s query
    finds the combine's six `tgmm` calls (forward and `_take_rows`'
    transpose in each of three capacities; the layer's remat reruns no
    combine, its output is needed by nothing on the way back), each
    one-hot^T x rows over 64 tiles of 256 tokens, and none of the experts'
    grouped matmuls."""
    assert compiled["sdar_branches"] == [
        "branch_0_fun", "branch_1_fun", "branch_2_fun"]
    assert compiled["sdar_slot_rows"] == []
    assert compiled["moe_combine_kernels"] == 6
    assert compiled["moe_combine_kernels_are_the_sums"]
    assert compiled["moe_combine_other_hits_below_the_last_capacity"] == []
    # the experts' own kernels are there, and matched nothing
    assert compiled["sdar_custom_calls"] >= 4 + 9 + 6


def test_a_shares_row_moves_visit_live_row_tiles_as_compiled_for_v5e(compiled):
    """The same compiled layer: no `select` makes zeros for the dead rows of
    a capacity's `[cap, 2048]` buffer (three a capacity stood here until
    PR 45, `broadcast_select_fusion_bf16_32768_2048`, 0.41 ms each); no
    gather is as long as a capacity: each of the five a capacity
    (`_take_rows` forward, recomputed and as the combine's transpose, the
    combine's re-ordering gather forward and as `_take_rows`' transpose)
    gathers a row tile of 4,096 in the body of a `while` in a branch of the
    capacity switch, so its trips follow the live rows, and one DMA
    (`row_tile`) puts the tile into a buffer that nothing has written
    (`unwritten`): no broadcast fills a buffer, no `dynamic-update-slice`
    or copy moves one; and the step's only `conditional`s are the two
    switches, forward and backward, three capacities each, which the three
    `*_held_time_share` metrics count whole."""
    caps = [32768, 65536, 131072]
    assert compiled["sdar_caps"] == caps
    assert compiled["sdar_fill_selects"] == []
    assert compiled["sdar_row_gathers"] == [
        [4096, ["body", "branch_computations", "calls"]]] * 15
    assert compiled["sdar_row_move_calls"] == {
        "unwritten": [[cap, ["branch_computations"]]
                      for cap in caps for _ in range(5)],
        "row_tile": [[cap, ["body", "branch_computations"]]
                     for cap in caps for _ in range(5)]}
    assert compiled["sdar_buffer_copies"] == []
    assert compiled["sdar_conditionals"] == [3, 3]


def test_the_whole_dispatch_compiles_to_the_parents_program(compiled):
    """`test_moe_layer_backward_as_compiled_for_v5e`'s program, every
    expert held (train-olmoe-1chip's block: `_permute`, `_combine`,
    `sort_by_expert`), is instruction for instruction what the commit
    before PR 45 compiled to: a share's row moves changed, the whole
    dispatch runs none of them. The digest is of the optimised text less
    paths, source lines and the Pallas calls' payloads (`same_program`); a
    PR that means to change this block pins its own."""
    assert compiled["moe_layer_program"] == (
        "a547498e153528770e893a4dbecc036ed53f538ca2321e526c3bca9df27fbe09")
