"""ray_tpu.ops — Pallas TPU kernels and their reference implementations.

The hot ops of the ML stack (SURVEY.md §7: 'Pallas kernels for the hot ops').
The reference has no kernels of its own (it orchestrates torch/vLLM); on TPU
these are ours. Every op has a pure-jnp reference path used on CPU and as the
numerical oracle in tests; the Pallas path engages on TPU.
"""
import importlib

_EXPORTS = {
    "flash_attention": "flash_attention",
    "mha_reference": "flash_attention",
}
_MODULES = ("flash_attention", "ragged_paged_attention")

__all__ = list(_EXPORTS) + list(_MODULES)


def __getattr__(name):
    if name in _EXPORTS:
        return getattr(importlib.import_module(f".{_EXPORTS[name]}",
                                               __name__), name)
    if name in _MODULES:
        return importlib.import_module(f".{name}", __name__)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
