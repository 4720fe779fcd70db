"""The Backend contract — one RTCG pipeline, pluggable execution targets.

The source paper's central architectural claim is that a run-time
code-generation pipeline splits cleanly into a *target-independent*
front half (snippet translation, caching, autotuning, fusion planning)
and a *target-specific* back half (compile-and-launch) — PyCUDA and
PyOpenCL share everything but the last step.  This module pins that
split down for the reproduction:

  * the kernel families (`elementwise`/`reduction`/`scan`) produce
    **specs** — frozen descriptions of translated snippets plus argument
    metadata, with no compilation machinery attached;
  * the specs *lower* into the kernel IR (`repro.core.ir`) and a chain
    of pure transformations (tile / split / transpose_layout / tag)
    schedules it — that pipeline lives HERE, in the concrete
    ``*_driver`` methods, shared by every backend;
  * a `Backend` turns the transformed IR into a compiled *driver*:
    ``render_ir`` (IR -> source text) → compile (source -> jitted
    callable) → ``build_*`` (the driver: pad operands, call, slice).

Drivers keep the dispatch-engine calling conventions:

  * flat elementwise/reduction: ``driver(n, flat_args)``
  * row-segmented (axis=-1):    ``driver(b, n, flat_args)``
  * column-segmented (axis=0):  ``driver(b, n, flat_args)`` over the
    *domain* geometry (b = outputs, n = reduced length) with operands
    passed in storage order — the IR's ``transpose_layout`` tells the
    driver to bind full operands transposed;
  * scan:                       ``driver(n, x)``

Backends also carry a capability/fingerprint record (`fingerprint()`)
so caches, tuning winners and benchmark rows can be keyed per backend —
the paper's environment fingerprint gains a "which toolkit" dimension,
exactly like the CUDA-vs-OpenCL comparisons treat the backend itself as
a measured variable.
"""

from __future__ import annotations

import abc
from dataclasses import dataclass, field
from typing import Any, Callable


@dataclass(frozen=True)
class ElementwiseSpec:
    """Snippet + argument description of one elementwise kernel.

    ``body_lines`` are the translated jnp statements (they reference
    operands by bare name, scalar args as plain python scalars, the
    block shape as ``_BLK`` and — flat layout only — the global element
    index ``i``).  ``arg_meta`` is ``(name, jnp dtype, kind)`` per
    positional argument with kind in scalar|full|row|col.
    """

    name: str
    arg_meta: tuple            # ((name, dtype, kind), ...)
    scalar_names: tuple
    loaded_vectors: tuple      # vector/broadcast names read by the body
    body_lines: tuple
    out_names: tuple
    out_dtypes: tuple
    needs_i: bool
    preamble: str = ""
    # pallas-only (other backends ignore it); no default: a spec must
    # carry the flag its family resolved (`platform.interpret_mode`)
    interpret: bool = field(kw_only=True)

    def token(self) -> list:
        """JSON-able identity for content-addressed caching."""
        return ["eltwise", self.name,
                [(m[0], str(m[1]), m[2]) for m in self.arg_meta],
                list(self.body_lines), list(self.out_names),
                [str(d) for d in self.out_dtypes], self.needs_i,
                self.preamble, self.interpret]


@dataclass(frozen=True)
class ReductionSpec:
    """Snippet + argument description of one (multi-accumulator) map+reduce.

    ``outs`` holds one dict per accumulator: ``map_expr`` (translated),
    ``neutral`` (literal), ``block_reduce`` (e.g. ``jnp.sum``),
    ``combine`` (cross-grid-step fold — only sequential-grid backends
    use it) and ``dtype``.  ``axis`` is None (flat), -1 (row-segmented,
    one accumulator per row; later map_exprs may reference earlier
    accumulators as ``_acc<k>``) or 0 (column reduction over a 2-D
    operand — same segmented kernel over the transposed layout, see
    ``ir.transpose_layout``; arg kinds stay in STORAGE orientation).
    """

    name: str
    arg_meta: tuple
    scalar_names: tuple
    loaded_vectors: tuple
    prelude_lines: tuple       # hoisted CSE assignments, pre-translated
    outs: tuple                # (dict(map_expr, neutral, block_reduce, combine, dtype), ...)
    multi: bool
    axis: Any = None           # None | -1 | 0
    preamble: str = ""
    interpret: bool = field(kw_only=True)

    def token(self) -> list:
        # repr(axis) keeps None/-1/0 distinct (`axis or 0` collapsed
        # None and 0 — harmless pre-IR, a key collision once axis=0
        # column reductions exist)
        return ["reduce", self.name,
                [(m[0], str(m[1]), m[2]) for m in self.arg_meta],
                list(self.prelude_lines),
                [sorted(o.items()) for o in self.outs],
                self.multi, repr(self.axis), self.preamble, self.interpret]


@dataclass(frozen=True)
class ScanSpec:
    """Description of one prefix scan: combine op + neutral + dtype."""

    name: str
    dtype: str                 # jnp dtype name, e.g. "float32"
    neutral: str               # numeric literal
    cumop: str                 # e.g. "jnp.cumsum"
    binop: str                 # "+", "*", "jnp.maximum", "jnp.minimum"
    exclusive: bool
    interpret: bool = field(kw_only=True)

    def token(self) -> list:
        return ["scan", self.name, self.dtype, self.neutral, self.cumop,
                self.binop, self.exclusive, self.interpret]


def binop_apply(binop: str, a: str, b: str) -> str:
    """Apply a combine operator snippet ("+", "*", "jnp.maximum", ...)
    to two operand strings — shared by every backend's scan renderer."""
    if binop in ("+", "*"):
        return f"({a} {binop} {b})"
    return f"{binop}({a}, {b})"


class Backend(abc.ABC):
    """One execution target of the RTCG pipeline (lower→render→launch).

    Concrete backends are stateless singletons (see the package
    registry); every compiled driver is cached by the dispatch engine
    under a backend-qualified key, so two backends never share or
    clobber each other's drivers.

    The ``*_driver`` entry points are CONCRETE here: they run the
    shared lowering pipeline (spec -> `repro.core.ir.KernelIR` -> a
    transformation chain: ``tag_parallel`` the independent axis,
    ``transpose_layout`` for axis=0 reductions, ``tile``/``split`` for
    the block schedule) and hand the transformed IR to the backend's
    abstract ``build_*`` methods.  Backends never see specs — only IR.
    """

    #: registry name; also the tag on dispatch counters and bench rows
    name: str = "abstract"

    #: whether ``block_rows``/``block_n`` changes the *generated code*
    #: (pallas: yes — the block is the BlockSpec tile; xla: no — code
    #: depends only on the padded operand shape).  Kernel families drop
    #: the block size from dispatch keys of insensitive backends so
    #: tuning candidates that share a padded shape share one driver.
    block_sensitive: bool = True

    @abc.abstractmethod
    def fingerprint(self) -> dict:
        """Capability/version record — cache-key material and bench
        metadata.  Must differ between any two backends."""

    # ================= shared lowering pipeline (spec -> IR -> build)
    def elementwise_driver(self, spec: ElementwiseSpec, *, bucket: int,
                           block_rows: int) -> Callable:
        """Compile one flat-layout driver: ``driver(n, flat_args) ->
        [flat outputs]`` serving every ``n`` whose padded rows fit
        ``bucket``."""
        from repro.core import ir
        from repro.core.platform import LANES

        kir = ir.lower_elementwise(spec, rows=bucket, lanes=LANES)
        kir = ir.tag_parallel(kir, "rows")
        kir = ir.tile(kir, "rows", block_rows)
        drv = self.build_elementwise(kir)
        ir.mark_rendered(kir)
        return drv

    def elementwise_rows_driver(self, spec: ElementwiseSpec, *, brows: int,
                                ncols: int, block_rows: int,
                                ragged: bool = False) -> Callable:
        """Compile one row-layout driver: ``driver(b, n, flat_args) ->
        [(b, n) outputs]`` serving every ``(B, N)`` in the bucket pair.
        ``ragged=True`` adds a leading per-row length operand; the
        driver gains ``row_lens=`` and masks each row's stores at its
        own length (padding beyond it reads as zeros)."""
        from repro.core import ir

        kir = ir.lower_elementwise(spec, rows=brows, lanes=ncols,
                                   layout="rows", ragged=ragged)
        kir = ir.tag_parallel(kir, "rows")
        kir = ir.tile(kir, "rows", block_rows)
        drv = self.build_elementwise_rows(kir)
        ir.mark_rendered(kir)
        return drv

    def reduction_driver(self, spec: ReductionSpec, *, bucket: int,
                         block_rows: int) -> Callable:
        """Compile one flat map+reduce driver: ``driver(n, flat_args)``
        returning a scalar (or tuple of scalars when ``spec.multi``).
        The rows axis stays SEQUENTIAL: grid steps accumulate."""
        from repro.core import ir
        from repro.core.platform import LANES

        kir = ir.lower_reduction(spec, rows=bucket, cols=LANES)
        kir = ir.tile(kir, "rows", block_rows)
        drv = self.build_reduction(kir)
        ir.mark_rendered(kir)
        return drv

    def reduction_rows_driver(self, spec: ReductionSpec, *, brows: int,
                              ncols: int, block_rows: int,
                              ragged: bool = False) -> Callable:
        """Compile one segmented driver: ``driver(b, n, flat_args)``
        returning (b,)-shaped outputs (tuple when ``spec.multi``).

        ``brows``/``ncols`` are DOMAIN buckets (independent outputs x
        reduced length).  For ``spec.axis == 0`` the domain is the
        transpose of the stored arrays, so ``transpose_layout`` joins
        the chain: arg kinds swap row<->col and the driver binds full
        operands transposed.  ``ragged=True`` replaces the shared
        runtime ``n`` scalar with a per-row length vector (the driver
        gains ``row_lens=``); rows layout only, and incompatible with
        the transposed axis=0 form (lengths segment the reduced axis,
        which axis=0 stores as rows)."""
        from repro.core import ir

        if ragged and spec.axis == 0:
            raise ValueError("ragged reduction is axis=-1 only "
                             "(axis=0 reduces across the stored rows)")
        kir = ir.lower_reduction(spec, rows=brows, cols=ncols,
                                 layout="rows", ragged=ragged)
        if spec.axis == 0:
            kir = ir.transpose_layout(kir)
        kir = ir.tag_parallel(kir, "rows")
        kir = ir.tile(kir, "rows", block_rows)
        drv = self.build_reduction_rows(kir)
        ir.mark_rendered(kir)
        return drv

    def scan_driver(self, spec: ScanSpec, *, grid: int,
                    block_n: int) -> Callable:
        """Compile one prefix-scan driver: ``driver(n, x) -> flat out``.
        The stream axis splits into (blocks x elements); the inner axis
        is parallel within a block, the outer carries the prefix."""
        from repro.core import ir

        kir = ir.lower_scan(spec, n=grid * block_n)
        kir = ir.split(kir, "stream", block_n)
        kir = ir.tag_parallel(kir, "stream.i")
        drv = self.build_scan(kir)
        ir.mark_rendered(kir)
        return drv

    # ------------- render compatibility wrappers (introspection path)
    def render_elementwise(self, spec: ElementwiseSpec, block_rows: int,
                           ncols: int | None = None):
        """Source text for an elementwise spec at one block config —
        kept for `ElementwiseKernel.render` introspection; the IR is
        the real input (``render_ir``)."""
        from repro.core import ir
        from repro.core.platform import LANES

        kir = ir.lower_elementwise(spec, rows=block_rows,
                                   lanes=ncols if ncols is not None else LANES,
                                   layout="flat" if ncols is None else "rows")
        kir = ir.tag_parallel(kir, "rows")
        kir = ir.tile(kir, "rows", block_rows)
        return self.render_ir(kir)

    def render_reduction(self, spec: ReductionSpec, block_rows: int,
                         ncols: int | None = None):
        from repro.core import ir
        from repro.core.platform import LANES

        if spec.axis is None:
            kir = ir.lower_reduction(spec, rows=block_rows, cols=LANES)
        else:
            kir = ir.lower_reduction(spec, rows=block_rows, cols=ncols,
                                     layout="rows")
            if spec.axis == 0:
                kir = ir.transpose_layout(kir)
            kir = ir.tag_parallel(kir, "rows")
        kir = ir.tile(kir, "rows", block_rows)
        return self.render_ir(kir)

    def render_scan(self, spec: ScanSpec):
        from repro.core import ir

        return self.render_ir(ir.lower_scan(spec, n=0))

    # =========================== backend obligations (IR in, code out)
    @abc.abstractmethod
    def render_ir(self, kir) -> Any:
        """Render a transformed `KernelIR` to source text (a str, or
        the backend's per-pass tuple for scans)."""

    @abc.abstractmethod
    def build_elementwise(self, kir) -> Callable:
        """Assemble the flat elementwise driver from a tiled IR."""

    @abc.abstractmethod
    def build_elementwise_rows(self, kir) -> Callable:
        """Assemble the row-layout elementwise driver from a tiled IR."""

    @abc.abstractmethod
    def build_reduction(self, kir) -> Callable:
        """Assemble the flat map+reduce driver from a tiled IR."""

    @abc.abstractmethod
    def build_reduction_rows(self, kir) -> Callable:
        """Assemble the segmented reduction driver from a tiled IR
        (honoring ``kir.transposed`` at operand-bind time)."""

    @abc.abstractmethod
    def build_scan(self, kir) -> Callable:
        """Assemble the prefix-scan driver from a split IR."""


def bind_row_operand(kind: str, name: str, arg, dt, b: int, n: int,
                     brows: int, ncols: int, transposed: bool = False):
    """Shared bind step for segmented drivers: reorder a stored operand
    into DOMAIN order (transposed layouts flip full operands; broadcast
    vectors are 1-D either way), then bucket-pad it.  ``b``/``n`` are
    domain counts (outputs x reduced length)."""
    from repro.core.platform import pad_row_operand
    import jax.numpy as jnp

    if transposed and kind == "full":
        arg = jnp.asarray(arg).reshape(n, b).T
    return pad_row_operand(kind, name, arg, dt, b, n, brows, ncols)
