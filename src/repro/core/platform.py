"""Shared platform/layout vocabulary for the kernel families and backends.

Before the backend abstraction (PR 4) these helpers lived in
`elementwise.py` and were imported *sideways* by `reduction.py` and
`scan.py` — one kernel family reaching into a sibling for layout
constants.  They are not elementwise-specific: the lane width, dtype
canonicalization, operand classification and padding rules are the
shared contract between the *snippet layer* (kernel families describing
what to compute) and the *backend layer* (`repro.core.backends`,
deciding how to compile and launch it).  This module is that contract's
home; it depends only on jax/numpy and `snippets` — never on a kernel
family or a backend.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from pathlib import Path
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import snippets

LANES = 128  # VPU lane count — the innermost slicing axis on TPU.
SUBLANES = 8  # sublane count of a float32 VREG tile.
DEFAULT_BLOCK_ROWS = SUBLANES


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def interpret_mode() -> bool:
    """Whether Pallas kernels run in the interpreter: exactly when the
    default backend is not a TPU.  Every Pallas call site resolves its
    ``interpret`` flag here, so a chip run never interprets."""
    return not on_tpu()


#: Published per-chip peaks, keyed by ``jax.Device.device_kind``.
#: Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
#: 16 GB HBM at 819 GB/s; 128 MiB of VMEM per TensorCore).
PEAKS: dict = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9, "vmem_bytes": 128 << 20},
}
#: The chip the analytic models score for when no TPU is attached
#: (interpret mode and compile rehearsals target it).
TARGET_DEVICE_KIND = "TPU v5 lite"


def device_peaks(device_kind: "str | None" = None) -> dict:
    """Peaks of ``device_kind`` — by default the first JAX device's, or
    `TARGET_DEVICE_KIND` when that device is not a TPU.  A TPU missing
    from `PEAKS` raises: scoring it with another chip's numbers would
    rank configurations for the wrong hardware."""
    if device_kind is None:
        dev = jax.devices()[0]
        device_kind = (dev.device_kind if dev.platform == "tpu"
                       else TARGET_DEVICE_KIND)
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add its "
                       "published numbers to platform.PEAKS") from None


#: Root of the checkout this package runs from (``src/repro/core/..``).
REPO_ROOT = Path(__file__).resolve().parents[3]


def configure_compile_cache() -> str:
    """Place JAX's persistent compilation cache; entry points call this
    once at start-up, library modules never.  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX already uses it and nothing
    else is set; otherwise the cache is the fixed ``.jax_cache/`` at the
    repo root (a fixed path: the directory is part of the cache key, so
    one that moves never hits).  Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def canonical_dtype(dtype):
    """Respect jax_enable_x64: float64 -> float32 when x64 is off."""
    return jnp.dtype(jax.dtypes.canonicalize_dtype(jnp.dtype(dtype)))


# ------------------------------------------------------- argument kinds
@dataclass(frozen=True)
class VectorArg:
    dtype: Any
    name: str

    @property
    def jnp_dtype(self):
        return canonical_dtype(self.dtype)


@dataclass(frozen=True)
class ScalarArg:
    dtype: Any
    name: str

    @property
    def jnp_dtype(self):
        return canonical_dtype(self.dtype)


@dataclass(frozen=True)
class BroadcastArg:
    """Broadcast vector argument of a *row-layout* kernel over ``(B, N)``
    operands: ``kind='row'`` binds a length-B vector as a ``(B, 1)``
    block (a per-row reduced value re-entering fused elementwise code),
    ``kind='col'`` binds a length-N vector as a ``(1, N)`` block (a
    per-feature weight shared by every row).  In snippets the name is
    referenced bare (no ``[i]``) or as ``name[i]`` — either way jnp
    broadcasting inside the kernel stretches it across the block."""

    dtype: Any
    name: str
    kind: str = "row"  # 'row' -> (B, 1) | 'col' -> (1, N)

    @property
    def jnp_dtype(self):
        return canonical_dtype(self.dtype)


def arg_kind(a) -> str:
    if isinstance(a, ScalarArg):
        return "scalar"
    if isinstance(a, BroadcastArg):
        return a.kind
    return "full"


def parse_arguments(arguments) -> list:
    if isinstance(arguments, str):
        out = []
        for name, dtype, is_vec in snippets.parse_c_arguments(arguments):
            out.append(VectorArg(dtype, name) if is_vec else ScalarArg(dtype, name))
        return out
    return list(arguments)


# ------------------------------------------------ geometry + padding
def rows_geometry(first_vec) -> tuple[int, int]:
    """(batch rows, row length) of the leading full vector operand."""
    shape = first_vec.shape
    n = int(shape[-1])
    b = max(1, int(np.prod(shape[:-1]))) if len(shape) > 1 else 1
    return b, n


def pad_flat_operand(kind: str, name: str, arg, dt, n: int,
                     bucket: int, lanes: int = LANES):
    """Validate one flat-layout operand against the element count ``n``
    and zero-pad it to its bucketed ``(bucket, lanes)`` block shape
    (padding must never hide a size bug)."""
    if kind == "scalar":
        return jnp.full((1, 1), arg, dtype=dt)
    v = jnp.ravel(jnp.asarray(arg))
    if v.size != n:
        raise ValueError(
            f"vector argument {name!r} has {v.size} elements, "
            f"expected {n} (size of the first vector argument)")
    padded_size = bucket * lanes
    if n != padded_size:
        v = jnp.pad(v, (0, padded_size - n))
    return v.reshape(bucket, lanes)


def pad_row_operand(kind: str, name: str, arg, dt, b: int, n: int,
                    brows: int, ncols: int):
    """Validate one operand against the (b, n) geometry and zero-pad it
    to its bucketed block shape (padding must never hide a size bug)."""
    if kind == "scalar":
        return jnp.full((1, 1), arg, dtype=dt)
    v = jnp.asarray(arg)
    if kind == "full":
        if v.size != b * n:
            raise ValueError(f"vector argument {name!r} has {v.size} "
                             f"elements, expected {b}x{n}")
        return jnp.pad(v.reshape(b, n), ((0, brows - b), (0, ncols - n)))
    if kind == "row":
        if v.size != b:
            raise ValueError(f"per-row argument {name!r} has {v.size} "
                             f"elements, expected {b} rows")
        return jnp.pad(v.reshape(b, 1), ((0, brows - b), (0, 0)))
    if v.size != n:
        raise ValueError(f"per-col argument {name!r} has {v.size} "
                         f"elements, expected row length {n}")
    return jnp.pad(v.reshape(1, n), ((0, 0), (0, ncols - n)))
