"""Model zoo: JAX pytree models with logical sharding annotations.

Each model module exposes a Config dataclass, `init(config, key)`,
`param_logical_axes(config)` for the parallel layer and `loss_fn` (what
`train/` and the benchmark's `program` group ask of it), `forward_hidden`,
and `forward` where something wants logits. Models are plain pytrees — no
framework object wrap — so donation, sharding, and checkpointing stay
trivial. A model module is built from the layer library (`blocks`, `experts`,
`mixers`, `layer_pattern`) and imports no other model module.
"""

from ray_tpu.models import llama  # noqa: F401
from ray_tpu.models import mlp  # noqa: F401
