"""TPU-native LLM inference: bucketed prefill + continuous batching decode.

The reference has no in-repo inference engine (Ray Serve delegates LLM
serving to user code); SURVEY §7 lists "async serving on TPU: batching +
compiled-shape management (bucketing) in Serve replicas" as a required
hard part — this package supplies it.
"""

_NAMES = {
    "GenerationConfig": "ray_tpu.inference.engine",
    "sample_token": "ray_tpu.inference.sampling",
}


def __getattr__(name):
    # the sampler imports jax at module level; resolving on first use keeps
    # `import ray_tpu.inference` (and serve.llm, which imports it) jax-free
    # for drivers that only bind a deployment.
    if name in _NAMES:
        import importlib

        return getattr(importlib.import_module(_NAMES[name]), name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
