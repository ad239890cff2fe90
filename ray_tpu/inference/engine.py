"""What the paged engine and its callers share: the sampling settings of a
generation, the default prefill buckets, and the tensor-parallel layout of
llama-family weights for decode. The engine itself is
`ray_tpu.inference.paged_engine.PagedInferenceEngine`.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


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
