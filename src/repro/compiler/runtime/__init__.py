"""Pluggable executor backends for compiled Programs.

  base.py    — :class:`ExecutorBackend` interface + shared binding,
               validation, chaining; error taxonomy.
  golden.py  — :class:`GoldenExecutor`: contract-checking reference
               interpreter (bit-exact vs ``core/hetero_linear.py``).
  pallas.py  — :class:`PallasExecutor`: fused fast path, one
               split-aware ``kernels`` call per *layer* (im2col-free
               convs; per-program JIT cache keyed on the program
               fingerprint).
  multi.py   — :class:`MultiDeviceExecutor`: steps a
               ``partition.MultiDeviceProgram`` bundle, one backend
               executor per device, with the cross-device hand-off.

Select by name via :func:`get_backend` (the CLI's ``--backend`` flag
resolves here). To add a backend: subclass ``ExecutorBackend``,
implement ``_run_core`` (one partition's tiles; ``GoldenExecutor``)
or override ``run_layer`` (the whole layer; ``PallasExecutor``), and
register it in :data:`BACKENDS`.
"""
from repro.compiler.runtime.base import (
    ExecutionError,
    ExecutorBackend,
    LayerWeights,
    apply_pool,
    bind_synthetic,
    chain_layers,
    im2col_patches,
    requantize,
    requantize_rows,
    spatialize,
    synthetic_weights,
)
from repro.compiler.runtime.golden import GoldenExecutor
from repro.compiler.runtime.multi import MultiDeviceExecutor, global_layers
from repro.compiler.runtime.pallas import PallasExecutor
from repro.compiler.runtime.session import (
    DecodeSession,
    ExecutorSession,
    ReferenceSession,
    decode_step_ref,
    synthetic_decode_arrays,
)

BACKENDS: dict[str, type[ExecutorBackend]] = {
    GoldenExecutor.name: GoldenExecutor,
    PallasExecutor.name: PallasExecutor,
}


def get_backend(name: str) -> type[ExecutorBackend]:
    """Resolve an executor backend class by registry name."""
    try:
        return BACKENDS[name]
    except KeyError:
        raise ValueError(
            f"unknown executor backend {name!r}; available: "
            f"{sorted(BACKENDS)}") from None


__all__ = [
    "BACKENDS", "DecodeSession", "ExecutionError", "ExecutorBackend",
    "ExecutorSession", "GoldenExecutor", "LayerWeights",
    "MultiDeviceExecutor", "PallasExecutor", "ReferenceSession",
    "apply_pool", "bind_synthetic", "chain_layers", "decode_step_ref",
    "get_backend", "global_layers", "im2col_patches", "requantize",
    "requantize_rows", "spatialize", "synthetic_decode_arrays",
    "synthetic_weights",
]
