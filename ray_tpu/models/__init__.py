"""ray_tpu.models — TPU-native model zoo.

The reference ships no model implementations of its own (models live in
torch/vLLM which it orchestrates); this package provides the JAX-native
models the framework's Train/Serve/RLlib stacks run. All models follow the
same contract:

  cfg        — frozen dataclass, hashable (usable as a jit static arg)
  init(rng, cfg)            -> params pytree
  apply(params, inputs, cfg) -> outputs (pure; jit/pjit-friendly)
  logical_axes(cfg)          -> pytree of logical-axis tuples matching params
                                (resolved by parallel.sharding rules)
"""
import importlib

_MODULES = ("llama", "mla_moe", "resnet")
__all__ = list(_MODULES)


def __getattr__(name):
    if name in _MODULES:
        return importlib.import_module(f".{name}", __name__)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
