"""ScanKernel — generated prefix-scan kernels (PyCUDA's pycuda.scan).

PyCUDA ships Inclusive/ExclusiveScanKernel alongside ElementwiseKernel
and ReductionKernel; the combine operator comes from a C-like snippet
("a+b", "fmaxf(a,b)").  The family describes the scan (`ScanSpec`:
combine op, neutral, dtype, exclusivity) and hands it to an execution
`Backend` (`repro.core.backends`):

  * ``pallas``: the classic two-pass blocked scan, both passes generated
    from templates — per-block inclusive scan + block totals, a tiny
    host exclusive combine over the totals, then a carry-offset pass;
  * ``xla``: one associative cumulative op over the whole padded stream.

The generated source is element-count free; drivers are compiled per
power-of-two *grid bucket* (`repro.core.dispatch`) with neutral-element
padding on the way in and slicing on the way out, and shared across
instances through the backend-keyed dispatch LRU.

The block length ``block_n`` is the scan's tunable (the analogue of
``block_rows`` elsewhere): ``autotune()`` wires the shared `Autotuner`
with ``signature_fn=dispatch.bucketed_signature`` and records the
winner per ``(backend, dispatch.n_bucket)``, so later calls in the same
shape bucket pick it up automatically.
"""

from __future__ import annotations

import re
from typing import Any

import jax.numpy as jnp
import numpy as np

from repro.core import backends, dispatch
from repro.core.backends.base import ScanSpec
from repro.core.cache import stable_hash
from repro.core.platform import canonical_dtype, interpret_mode

_SCAN_OPS = {
    "a+b": ("jnp.cumsum", "+", "0"),
    "b+a": ("jnp.cumsum", "+", "0"),
    "max(a,b)": ("jax.lax.cummax", "jnp.maximum", "-3e38"),
    "fmaxf(a,b)": ("jax.lax.cummax", "jnp.maximum", "-3e38"),
    "min(a,b)": ("jax.lax.cummin", "jnp.minimum", "3e38"),
    "fminf(a,b)": ("jax.lax.cummin", "jnp.minimum", "3e38"),
    "a*b": ("jnp.cumprod", "*", "1"),
}


class ScanKernel:
    """Generated blocked prefix scan.

    >>> cumsum = ScanKernel(np.float32, "a+b", neutral="0")
    >>> cumsum(x)           # inclusive by default
    """

    def __init__(self, dtype, scan_expr: str, neutral: str | None = None,
                 name: str = "scan", exclusive: bool = False,
                 block_n: int = 4096, interpret: bool | None = None,
                 backend: "str | None" = None):
        key = re.sub(r"\s", "", scan_expr)
        if key not in _SCAN_OPS:
            raise NotImplementedError(
                f"scan_expr {scan_expr!r}; supported: {sorted(_SCAN_OPS)}")
        self.cumop, self.binop, default_neutral = _SCAN_OPS[key]
        self.neutral = neutral if neutral is not None else default_neutral
        self.dtype = canonical_dtype(dtype)
        self.name = re.sub(r"\W", "_", name)
        self.exclusive = exclusive
        self.block_n = block_n
        self.interpret = interpret_mode() if interpret is None else interpret
        self.backend = backend  # None: resolve REPRO_BACKEND per call
        self.spec = ScanSpec(
            name=self.name,
            dtype=str(self.dtype),
            neutral=self.neutral,
            cumop=self.cumop,
            binop=self.binop,
            exclusive=self.exclusive,
            interpret=self.interpret,
        )
        self._content_key = stable_hash(self.spec.token())
        self._tuned: dict = {}      # (backend, n_bucket) -> tuned block_n

    def _pick_block_n(self, n: int, block_n: int | None, be_name: str) -> int:
        if block_n:
            return block_n
        from repro.core import autotune
        bucket = dispatch.n_bucket(n)
        tuned = self._tuned.get((be_name, bucket))
        return (tuned
                or autotune.sequence_param(f"scan.{self.name}", be_name,
                                           bucket, "block_n")
                or self.block_n)

    def __call__(self, x, block_n: int | None = None,
                 backend: "str | None" = None):
        be = backends.get_backend(backend or self.backend)
        n = int(getattr(x, "size", 0)) or int(np.prod(x.shape))
        bn = self._pick_block_n(n, block_n, be.name)
        grid = dispatch.next_pow2(-(-n // bn))
        # block-insensitive backends only care about the padded stream
        # length grid*bn, so block_n candidates sharing it share a driver
        key = ("scan", be.name, self._content_key,
               (grid, bn) if be.block_sensitive else (grid * bn,))
        drv = dispatch.get_or_build(
            key, lambda: be.scan_driver(self.spec, grid=grid, block_n=bn),
            backend=be.name, name=self.name, bucket=(grid * bn,))
        out = dispatch.run_with_retries(
            lambda: drv(n, x), site="launch", backend=be.name,
            family=self.name, bucket=(grid * bn,)).reshape(x.shape)
        dispatch.record_launch(be.name)  # after the driver: failed launches don't count
        return out

    # -- tuning ------------------------------------------------------------
    def block_cost(self, params: dict, args) -> "Any":
        """Analytic `BlockCost` of one config — hybrid-mode pre-pruner."""
        from repro.core.autotune import BlockCost

        bn = params["block_n"]
        x = args[0]
        n = int(getattr(x, "size", 0)) or int(np.prod(x.shape))
        grid = dispatch.next_pow2(-(-n // bn))
        pn = grid * bn
        itemsize = jnp.dtype(self.dtype).itemsize
        return BlockCost(
            flops=float(2 * pn),
            # pass 1 reads + writes, pass 2 reads + writes
            hbm_bytes=float(4 * pn * itemsize),
            vmem_bytes=float(3 * bn * itemsize),
            grid=2 * grid,
        )

    def autotune(self, x, candidates: list[dict] | None = None,
                 measure: str = "hybrid", cache=None, repeats: int = 3,
                 warmup: int = 1, prune_keep: int | None = None,
                 backend: "str | None" = None):
        """Tune ``block_n`` for the *bucket* of this input.

        Same contract as the other kernel families: the winner is
        recorded per ``(backend, dispatch.n_bucket)`` and the
        tuning-cache key uses `dispatch.bucketed_signature` plus the
        backend name, so one tuning run covers every ``n`` in the
        bucket on that backend.
        """
        from repro.core.autotune import block_n_candidates, tune_per_bucket

        be = backends.get_backend(backend or self.backend)
        n = int(getattr(x, "size", 0)) or int(np.prod(x.shape))
        return tune_per_bucket(
            f"scan.{self.name}",
            builder=lambda block_n: (
                lambda a: self(a, block_n=block_n, backend=be)),
            cost_fn=self.block_cost,
            candidates=candidates or block_n_candidates(n),
            args=(x,), n=n, tuned=self._tuned, param="block_n",
            measure=measure, cache=cache, repeats=repeats, warmup=warmup,
            prune_keep=prune_keep, backend=be.name)


def InclusiveScanKernel(dtype, scan_expr, **kw):
    return ScanKernel(dtype, scan_expr, exclusive=False, **kw)


def ExclusiveScanKernel(dtype, scan_expr, neutral, **kw):
    return ScanKernel(dtype, scan_expr, neutral=neutral, exclusive=True, **kw)
