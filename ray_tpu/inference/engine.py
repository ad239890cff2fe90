"""InferenceEngine: slot-based continuous batching over a jitted decode step.

TPU design constraints this implements (SURVEY §7 "async serving on TPU"):

  * STATIC SHAPES — XLA compiles one program per shape. Prefill pads each
    prompt to a size bucket (powers of two up to max_len) so at most
    len(buckets) prefill programs exist; decode always runs the full
    [max_batch, 1] step regardless of how many slots are active.
  * CONTINUOUS BATCHING — requests occupy slots of a fixed-size batch;
    a finished request frees its slot for the next admission without
    stopping decode for the others (the "persistent batch" pattern).
  * DONATION — the KV cache is donated into each step so XLA updates it
    in place in HBM instead of copying [L,B,T,kv,K] every token.

Model-agnostic: any model exposing `forward_with_cache(params, tokens,
cache, lengths, config)` + `init_kv_cache` works (llama.py provides both).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.inference.sampling import sample_token


@dataclasses.dataclass(frozen=True)
class GenerationConfig:
    max_new_tokens: int = 64
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    eos_token_id: Optional[int] = None


def shard_params_for_inference(params, config, mesh, rules=None):
    """device_put llama-family params into their TP layout for a sharded
    engine (heads/mlp dims over the mesh's tp axis; everything else
    replicated — no fsdp at inference: weights are read-only)."""
    from ray_tpu.models.llama import param_logical_axes
    from ray_tpu.parallel.sharding import LogicalAxisRules, shard_params

    rules = rules or LogicalAxisRules().replace(
        embed=None, vocab=None)  # no fsdp/vocab sharding at decode
    return shard_params(params, param_logical_axes(config), mesh, rules)


def _default_buckets(max_len: int) -> Tuple[int, ...]:
    out, b = [], 64
    while b < max_len:
        out.append(b)
        b *= 2
    out.append(max_len)
    return tuple(out)


class InferenceEngine:
    def __init__(
        self,
        params: Any,
        config: Any,
        *,
        forward_with_cache: Optional[Callable] = None,
        init_kv_cache: Optional[Callable] = None,
        max_batch: int = 8,
        max_len: int = 1024,
        prefill_buckets: Optional[Tuple[int, ...]] = None,
        mesh: Any = None,
        decode_chunk: int = 16,
    ):
        """With `mesh`, decode runs tensor-parallel over it: pass params
        already sharded (see shard_params_for_inference) and the KV cache
        shards over the mesh's `tp` axis on its kv-heads dim — XLA
        propagates the layout through prefill/decode and inserts the ICI
        collectives (psum after wo/w_down) itself."""
        if forward_with_cache is None or init_kv_cache is None:
            from ray_tpu.models import llama

            forward_with_cache = forward_with_cache or llama.forward_with_cache
            init_kv_cache = init_kv_cache or llama.init_kv_cache
        self.params = params
        self.config = config
        self.max_batch = max_batch
        self.max_len = max_len
        self.buckets = prefill_buckets or _default_buckets(max_len)
        self._fwd = forward_with_cache
        self.mesh = mesh
        self.cache = init_kv_cache(config, max_batch, max_len)
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            tp = "tp" if mesh.shape.get("tp", 1) > 1 else None
            # [layers, batch, time, kv_heads, head_dim]: kv heads over tp
            kv_sharding = NamedSharding(
                mesh, PartitionSpec(None, None, None, tp, None))
            self.cache = jax.tree.map(
                lambda x: jax.device_put(x, kv_sharding), self.cache)
        # slot state (host side)
        self.lengths = np.zeros(max_batch, dtype=np.int32)
        self.free_slots = list(range(max_batch))
        self._key = jax.random.PRNGKey(0)

        def prefill_batch_impl(params, cache, tokens, slots, true_lens, key,
                          temperature=0.0, top_k=0, top_p=1.0):
            """Batched admission: tokens [N, bucket] padded prompts,
            slots [N] distinct slot indices, true_lens [N]. Prefills all
            N rows AND samples each row's first token on-device, so a
            whole admission wave is ONE dispatch + one [N]-token
            transfer (per-request prefill pays a host round trip per
            prompt)."""
            n, _ = tokens.shape
            t = cache["k"].shape[2]
            row_cache = {
                k: jnp.zeros((v.shape[0], n) + v.shape[2:], v.dtype)
                for k, v in cache.items()
            }
            logits, row_cache = self._fwd(
                params, tokens, row_cache, jnp.zeros((n,), jnp.int32),
                self.config)
            valid = (jnp.arange(t)[None, :]
                     < true_lens[:, None])[None, :, :, None, None]
            new_cache = {}
            for name in cache:
                updated = jnp.where(valid, row_cache[name], 0).astype(
                    cache[name].dtype)
                new_cache[name] = cache[name].at[:, slots].set(updated)
            last = logits[jnp.arange(n), true_lens - 1]  # [N, vocab]
            first = sample_token(last, key, temperature=temperature,
                                 top_k=top_k, top_p=top_p)
            return new_cache, first

        def decode_full_impl(params, cache, tokens, lengths, budget, active,
                        key, n_steps, eos_id, max_steps,
                        temperature=0.0, top_k=0, top_p=1.0):
            """The whole decode-sample-append loop in ONE compiled
            program (VERDICT r3 #1): a lax.while_loop runs up to
            `n_steps` (traced — no recompile per chunk length) decode
            steps with on-device sampling, per-slot budget/EOS/length
            tracking, and early exit when every slot is done. The host
            is out of the loop for the entire generation; the only
            transfer is the [max_steps, B] token block at the end.

            tokens [B,1]; budget [B] remaining new-token allowance;
            active [B] bool; eos_id traced int32 (-1 = no EOS).
            -> (cache, out [max_steps, B], executed_steps)."""
            t_max = cache["k"].shape[2]
            out0 = jnp.zeros((max_steps, tokens.shape[0]), jnp.int32)

            def cond(c):
                i, _, _, _, _, act, _, _ = c
                return (i < n_steps) & jnp.any(act)

            def body(c):
                i, cache, tok, lens, rem, act, k, out = c
                logits, cache = self._fwd(params, tok, cache, lens,
                                          self.config)
                k, sub = jax.random.split(k)
                nxt = sample_token(logits[:, -1], sub,
                                   temperature=temperature,
                                   top_k=top_k, top_p=top_p)
                out = jax.lax.dynamic_update_index_in_dim(
                    out, jnp.where(act, nxt, -1), i, 0)
                lens = jnp.where(act, lens + 1, lens)
                rem = jnp.where(act, rem - 1, rem)
                act = act & (rem > 0) & (nxt != eos_id) & (lens + 1 < t_max)
                return (i + 1, cache, nxt[:, None], lens, rem, act, k, out)

            i, cache, _, _, _, _, _, out = jax.lax.while_loop(
                cond, body,
                (jnp.int32(0), cache, tokens, lengths, budget, active,
                 key, out0))
            return cache, out, i

        def generate_wave(params, cache, tokens, slots, true_lens, budget,
                          key, n_steps, eos_id, max_steps,
                          temperature=0.0, top_k=0, top_p=1.0):
            """Fresh-batch fast path: batched prefill + first-token
            sampling + the ENTIRE decode loop in one compiled program —
            a full generate() is ONE dispatch and one result transfer
            instead of one host round trip per decode chunk."""
            t_max = cache["k"].shape[2]
            b = cache["k"].shape[1]
            key, pk, dk = jax.random.split(key, 3)
            cache, firsts = prefill_batch_impl(
                params, cache, tokens, slots, true_lens, pk,
                temperature=temperature, top_k=top_k, top_p=top_p)
            tok0 = jnp.zeros((b, 1), jnp.int32).at[slots, 0].set(firsts)
            lens0 = jnp.zeros((b,), jnp.int32).at[slots].set(true_lens)
            bud0 = jnp.zeros((b,), jnp.int32).at[slots].set(budget)
            act0 = (jnp.zeros((b,), bool).at[slots].set(
                (firsts != eos_id) & (true_lens + 1 < t_max))
                & (bud0 > 0))
            cache, out, executed = decode_full_impl(
                params, cache, tok0, lens0, bud0, act0, dk, n_steps,
                eos_id, max_steps=max_steps, temperature=temperature,
                top_k=top_k, top_p=top_p)
            return cache, firsts, out, executed

        self._prefill_batch = jax.jit(
            prefill_batch_impl, donate_argnums=(1,),
            static_argnames=("temperature", "top_k", "top_p"))
        self._decode_full = jax.jit(
            decode_full_impl, donate_argnums=(1,),
            static_argnames=("max_steps", "temperature", "top_k", "top_p"))
        self._generate_wave = jax.jit(
            generate_wave, donate_argnums=(1,),
            static_argnames=("max_steps", "temperature", "top_k", "top_p"))
        self.decode_chunk = max(1, decode_chunk)

    # -- internals ----------------------------------------------------------

    def _bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        raise ValueError(f"prompt of {n} tokens exceeds max_len={self.max_len}")

    def _release(self, slot: int) -> None:
        self.lengths[slot] = 0
        self.free_slots.append(slot)

    def _consume_block(self, out, executed, active, gen) -> Iterator[
            Tuple[int, int]]:
        """Walk a [steps, B] token block from the fused decode, yielding
        (req_idx, token) and releasing slots as their host-side done
        conditions fire (mirrors the device's active-mask logic, so the
        -1 filler rows past a slot's completion are never read)."""
        for step in range(int(executed)):
            if not active:
                break
            for slot in list(active):
                st = active[slot]
                self.lengths[slot] += 1
                token = int(out[step, slot])
                st["produced"] += 1
                st["current"] = token
                done = (
                    (gen.eos_token_id is not None
                     and token == gen.eos_token_id)
                    or st["produced"] >= gen.max_new_tokens
                    or self.lengths[slot] + 1 >= self.max_len)
                yield st["req"], token
                if done:
                    del active[slot]
                    self._release(slot)

    def _run_wave(self, pending, active, gen) -> Iterator[Tuple[int, int]]:
        """One-dispatch generation for a fresh same-bucket batch: prefill,
        first-token sampling, and the full decode run as a single
        compiled program (generate_wave)."""
        batch = pending[::-1]  # original submission order
        n = len(batch)
        bucket = self._bucket_for(max(len(p) for _, p in batch))
        slots = [self.free_slots.pop() for _ in range(n)]
        toks = np.zeros((n, bucket), dtype=np.int32)
        true_lens = np.zeros((n,), dtype=np.int32)
        for row, (_, prompt) in enumerate(batch):
            toks[row, :len(prompt)] = prompt
            true_lens[row] = len(prompt)
        budget = np.full((n,), gen.max_new_tokens - 1, dtype=np.int32)
        need = max(max(1, min(gen.max_new_tokens - 1,
                              self.max_len - 1 - len(p)))
                   for _, p in batch)
        max_steps = 1
        while max_steps < need:
            max_steps *= 2
        eos = gen.eos_token_id if gen.eos_token_id is not None else -1
        self._key, sub = jax.random.split(self._key)
        try:
            self.cache, firsts, out, executed = self._generate_wave(
                self.params, self.cache, jnp.asarray(toks),
                jnp.asarray(np.array(slots, np.int32)),
                jnp.asarray(true_lens), jnp.asarray(budget), sub,
                jnp.int32(need), jnp.int32(eos), max_steps=max_steps,
                temperature=gen.temperature, top_k=gen.top_k,
                top_p=gen.top_p)
            firsts, out, executed = jax.device_get((firsts, out, executed))
        except Exception:
            self.free_slots.extend(slots)
            raise
        for (req_idx, prompt), slot, first in zip(batch, slots, firsts):
            first = int(first)
            self.lengths[slot] = len(prompt)
            yield req_idx, first
            if ((gen.eos_token_id is not None
                 and first == gen.eos_token_id)
                    or self.lengths[slot] + 1 >= self.max_len):
                self._release(slot)
                continue
            active[slot] = {"req": req_idx, "produced": 1, "current": first}
        yield from self._consume_block(out, executed, active, gen)

    # -- public API ---------------------------------------------------------

    def generate_stream(
        self,
        prompts: List[List[int]],
        gen: Optional[GenerationConfig] = None,
    ) -> Iterator[Tuple[int, int]]:
        """Continuous-batching generation. Yields (request_index, token_id)
        pairs; requests are admitted as slots free up.

        Tokens arrive in BLOCKS, not one at a time: the fused decode runs
        a whole generation (or decode_chunk steps when requests are
        waiting) per dispatch, and this iterator drains each block as it
        lands. Per-token streaming would put a host round trip back into
        the decode loop — the opposite trade from what a TPU behind a
        dispatch latency wants."""
        gen = gen or GenerationConfig()
        for p in prompts:
            if not p:
                raise ValueError("cannot generate from an empty prompt")
        if not self.free_slots:
            # All slots are occupied — only possible when a previous
            # generate_stream iterator was abandoned mid-stream; refuse
            # rather than silently serving nothing.
            raise RuntimeError(
                "no free engine slots (an earlier generate_stream was "
                "abandoned mid-stream?); create a fresh engine")
        pending = list(enumerate(prompts))[::-1]  # stack of (req_idx, prompt)
        active: Dict[int, dict] = {}  # slot -> {req, produced, current}

        # Fresh-batch fast path: when every prompt fits one admission wave
        # (same bucket, enough free slots), run prefill + the whole decode
        # as ONE dispatch (generate_wave) instead of two.
        if (pending and len(pending) <= len(self.free_slots)
                and gen.max_new_tokens > 1
                and len({self._bucket_for(len(p)) for _, p in pending}) == 1):
            yield from self._run_wave(pending, active, gen)
            pending = []

        def admit_all():
            """Admit pending prompts in bucket-grouped WAVES: one
            prefill_batch dispatch per (bucket, group-size) instead of
            one prefill + one sample round trip per request."""
            while pending and self.free_slots:
                bucket = self._bucket_for(len(pending[-1][1]))
                batch: List[Tuple[int, List[int]]] = []
                while (pending and len(batch) < len(self.free_slots)
                       and self._bucket_for(len(pending[-1][1])) == bucket):
                    batch.append(pending.pop())
                n = len(batch)
                slots = [self.free_slots.pop() for _ in range(n)]
                toks = np.zeros((n, bucket), dtype=np.int32)
                true_lens = np.zeros((n,), dtype=np.int32)
                for row, (_, prompt) in enumerate(batch):
                    toks[row, :len(prompt)] = prompt
                    true_lens[row] = len(prompt)
                self._key, sub = jax.random.split(self._key)
                try:
                    self.cache, firsts = self._prefill_batch(
                        self.params, self.cache, jnp.asarray(toks),
                        jnp.asarray(np.array(slots, np.int32)),
                        jnp.asarray(true_lens), sub,
                        temperature=gen.temperature, top_k=gen.top_k,
                        top_p=gen.top_p)
                    firsts = np.asarray(firsts)
                except Exception:
                    self.free_slots.extend(slots)
                    raise
                for (req_idx, prompt), slot, first in zip(
                        batch, slots, firsts):
                    first = int(first)
                    self.lengths[slot] = len(prompt)
                    yield req_idx, first
                    # A prefill-sampled token can already terminate.
                    if ((gen.eos_token_id is not None
                         and first == gen.eos_token_id)
                            or gen.max_new_tokens <= 1
                            or self.lengths[slot] + 1 >= self.max_len):
                        self._release(slot)
                        continue
                    active[slot] = {"req": req_idx, "produced": 1,
                                    "current": first}

        yield from admit_all()
        while active:
            tokens = np.zeros((self.max_batch, 1), dtype=np.int32)
            budget = np.zeros(self.max_batch, dtype=np.int32)
            act = np.zeros(self.max_batch, dtype=bool)
            for slot, st in active.items():
                tokens[slot, 0] = st["current"]
                budget[slot] = gen.max_new_tokens - st["produced"]
                act[slot] = True
            # Run the WHOLE remaining generation in one dispatch unless
            # requests are waiting for a slot — slots can free early via
            # EOS, budget variance across admission waves, or per-slot
            # max_len caps, so cap at decode_chunk to keep admission
            # responsive whenever anything is pending.
            need = max(
                min(gen.max_new_tokens - st["produced"],
                    self.max_len - 1 - self.lengths[slot])
                for slot, st in active.items())
            need = max(1, need)
            if pending:
                need = min(need, self.decode_chunk)
            max_steps = 1
            while max_steps < need:
                max_steps *= 2
            self._key, sub = jax.random.split(self._key)
            eos = (gen.eos_token_id
                   if gen.eos_token_id is not None else -1)
            self.cache, out, executed = self._decode_full(
                self.params, self.cache, jnp.asarray(tokens),
                jnp.asarray(self.lengths), jnp.asarray(budget),
                jnp.asarray(act), sub, jnp.int32(need), jnp.int32(eos),
                max_steps=max_steps, temperature=gen.temperature,
                top_k=gen.top_k, top_p=gen.top_p)
            out, executed = jax.device_get((out, executed))
            n_before = len(active)
            yield from self._consume_block(out, executed, active, gen)
            if pending and len(active) < n_before:
                yield from admit_all()

    def generate(self, prompts: List[List[int]],
                 gen: Optional[GenerationConfig] = None) -> List[List[int]]:
        """-> new tokens per prompt (prompt not included)."""
        out: List[List[int]] = [[] for _ in prompts]
        for req_idx, token in self.generate_stream(prompts, gen):
            out[req_idx].append(token)
        return out
