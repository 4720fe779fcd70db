"""ElementwiseKernel — generated, tiled elementwise kernels (paper §5.2, Fig. 4).

The user supplies an argument list and a C-like snippet; the toolkit
supplies *loop slicing* and driver code.  On CUDA, loop slicing meant
thread/block decomposition; here the kernel family only *describes* the
computation — translated snippet body, argument metadata, output dtypes
(an `ElementwiseSpec`) — and hands it with a bucketed geometry to an
execution `Backend` (`repro.core.backends`):

  * ``pallas`` (default): flatten -> pad -> reshape to ``(rows, 128)``
    lanes -> tile rows into VMEM blocks -> 1-D grid, with
    ``block_rows`` as the tunable (the analogue of CUDA block size);
  * ``xla``: the same snippet jitted over the whole bucketed operand.

Faithful API surface (both paper variants):

    lin_comb = ElementwiseKernel(
        "float a, float *x, float b, float *y, float *z",
        "z[i] = a*x[i] + b*y[i]")

    lin_comb = ElementwiseKernel(
        [ScalarArg(x.dtype, "a"), VectorArg(x.dtype, "x"), ...],
        "z[i] = a*x[i] + b*y[i]")

Launch path: ``__call__`` goes through `repro.core.dispatch` — element
counts are rounded up to power-of-two row *buckets* so one compiled
driver (shared process-wide in an LRU, keyed per backend) serves every
``n`` in the bucket, and the hot path is a couple of integer ops plus a
cache lookup: no argument re-parsing, no dict construction, no
re-render.  Per-(backend, bucket) tuned ``block_rows`` (see `autotune`)
are applied automatically when the call site does not pin one.

Row layout (axis-aware fusion, PR 3): ``layout="rows"`` keeps ``(B, N)``
operands 2-D — blocks are ``(block_rows, ncols)`` row groups, buckets
cover *both* dimensions (`dispatch.bucket_batch` × `bucket_cols`), and
`BroadcastArg` inputs bind per-row ``(B, 1)`` or per-col ``(1, N)``
values that jnp broadcasting stretches across the block — how computed
row reductions and shared feature weights enter a fused 2-D epilogue.
"""

from __future__ import annotations

import re
from typing import Any

import jax.numpy as jnp
import numpy as np

from repro.core import backends, dispatch, snippets
from repro.core.backends.base import ElementwiseSpec
from repro.core.backends.pallas import row_block_specs  # compat re-export
from repro.core.cache import stable_hash
from repro.core.platform import (DEFAULT_BLOCK_ROWS, LANES, BroadcastArg,
                                 ScalarArg, VectorArg, arg_kind,
                                 canonical_dtype, interpret_mode, pad_row_operand,
                                 parse_arguments, rows_geometry)

# Compat aliases — these helpers lived here before the backend layer
# (PR 4); sibling kernel families and user code import them by the old
# names.  New code should import from `repro.core.platform`.
_canonical = canonical_dtype
_arg_kind = arg_kind
_parse_arguments = parse_arguments


class ElementwiseKernel:
    """Generate + cache a fused elementwise kernel from a C-like snippet."""

    def __init__(self, arguments, operation: str, name: str = "eltwise",
                 preamble: str = "", block_rows: int | None = None,
                 interpret: bool | None = None, layout: str = "flat",
                 backend: "str | None" = None):
        self.args = parse_arguments(arguments)
        self.operation = operation
        self.name = re.sub(r"\W", "_", name)
        self.preamble = preamble
        self.block_rows = block_rows
        self.interpret = interpret_mode() if interpret is None else interpret
        self.layout = layout
        self.backend = backend  # None: resolve REPRO_BACKEND per call

        self.scalar_args = [a for a in self.args if isinstance(a, ScalarArg)]
        self.vector_args = [a for a in self.args if isinstance(a, VectorArg)]
        self.bcast_args = [a for a in self.args if isinstance(a, BroadcastArg)]
        if layout not in ("flat", "rows"):
            raise ValueError(f"unknown layout {layout!r} (flat | rows)")
        if self.bcast_args and layout != "rows":
            raise ValueError("BroadcastArg requires layout='rows' "
                             "(per-row/per-col binding needs the 2-D layout)")
        self.out_names = snippets.written_names(operation)
        unknown = set(self.out_names) - {v.name for v in self.vector_args}
        if unknown:
            raise ValueError(f"snippet writes undeclared vectors: {sorted(unknown)}")
        if not self.out_names:
            raise ValueError("elementwise snippet writes no vector (need e.g. 'z[i] = ...')")
        self._body_lines, self._loaded = self._translate()
        if layout == "rows" and self._needs_i():
            raise ValueError("row-layout kernels have no flat element index "
                             "'i'; address data per block instead")
        # Launch fast path: everything derivable from the signature is
        # precomputed here so __call__ does no per-call parsing.
        names = [a.name for a in self.args]
        self._first_vec_pos = names.index(self.vector_args[0].name)
        self._arg_meta = tuple((a.name, a.jnp_dtype, arg_kind(a))
                               for a in self.args)
        self._out_positions = [names.index(o) for o in self.out_names]
        self._out_dtypes = [dict((v.name, v.jnp_dtype) for v in self.vector_args)[o]
                            for o in self.out_names]
        self.spec = ElementwiseSpec(
            name=self.name,
            arg_meta=self._arg_meta,
            scalar_names=tuple(s.name for s in self.scalar_args),
            loaded_vectors=tuple(self._loaded),
            body_lines=tuple(self._body_lines),
            out_names=tuple(self.out_names),
            out_dtypes=tuple(self._out_dtypes),
            needs_i=self._needs_i(),
            preamble=self.preamble,
            interpret=self.interpret,
        )
        self._content_key = stable_hash(self.spec.token())
        self._tuned: dict = {}      # (backend, bucket key) -> tuned block_rows

    # -- codegen ----------------------------------------------------------
    def _translate(self) -> tuple[list[str], list[str]]:
        body: list[str] = []
        vec_names = {v.name for v in self.vector_args}
        load_names = vec_names | {b.name for b in self.bcast_args}
        dtypes = {v.name: str(v.jnp_dtype) for v in self.vector_args}
        read: set[str] = set()
        stmts = snippets.split_statements(self.operation)
        # vectors read anywhere on an RHS (incl. read-modify-write outputs)
        for s in stmts:
            tgt, expr = snippets.translate_statement(s)
            for v in load_names:
                if re.search(rf"\b{re.escape(v)}\b", expr):
                    read.add(v)
        for s in stmts:
            tgt, expr = snippets.translate_statement(s)
            if tgt in vec_names:
                # keep written vectors in locals so later statements see
                # the updated value (CUDA in-place buffer semantics);
                # the template stores them to the out refs at the end.
                body.append(
                    f"{tgt} = jnp.broadcast_to(jnp.asarray({expr}), _BLK)"
                    f".astype(jnp.{dtypes[tgt]})"
                )
            elif tgt is not None:
                body.append(f"{tgt} = {expr}")
            else:
                body.append(expr)
        return body, sorted(read)

    def _needs_i(self) -> bool:
        probe = snippets._SUBSCRIPT_RE.sub(lambda m: m.group(1), self.operation)
        return bool(re.search(r"\bi\b", probe))

    def render(self, block_rows: int, ncols: int | None = None,
               backend: "str | None" = None) -> str:
        """Source this kernel's spec renders to on ``backend`` (debug/
        introspection surface; drivers render internally)."""
        return backends.get_backend(backend or self.backend).render_elementwise(
            self.spec, block_rows, ncols)

    # -- driver -----------------------------------------------------------
    def _pick_block_rows(self, n: int, block_rows: int | None,
                         be_name: str) -> int:
        if block_rows:
            return block_rows
        from repro.core import autotune
        bucket = dispatch.n_bucket(n)
        tuned = self._tuned.get((be_name, bucket))
        return (tuned
                or autotune.sequence_param(f"eltwise.{self.name}", be_name,
                                           bucket, "block_rows")
                or self.block_rows or dispatch.default_block_rows(n))

    def _rows_geometry(self, call_args) -> tuple[int, int]:
        return rows_geometry(call_args[self._first_vec_pos])

    def _call_rows(self, call_args, block_rows: int | None, be,
                   row_lens=None):
        from repro.core import autotune
        ragged = row_lens is not None
        b, n = self._rows_geometry(call_args)
        bucket = dispatch.rc_bucket(b, n, ragged=ragged)
        br = dispatch.batch_block(b, (
            block_rows or self._tuned.get((be.name, bucket))
            or autotune.sequence_param(f"eltwise.{self.name}", be.name,
                                       bucket, "block_rows")
            or self.block_rows or dispatch.default_batch_block(b)))
        brows = dispatch.bucket_batch(b, br)
        ncols = dispatch.bucket_cols(n)
        key = ("eltwise_rows", be.name, self._content_key, brows, ncols,
               br if be.block_sensitive else 0)
        if ragged:  # dense keys stay byte-identical
            key = key + ("R",)
        site_bucket = (brows, ncols, "R") if ragged else (brows, ncols)
        drv = dispatch.get_or_build(
            key,
            lambda: be.elementwise_rows_driver(self.spec, brows=brows,
                                               ncols=ncols, block_rows=br,
                                               ragged=ragged),
            backend=be.name, name=self.name, bucket=site_bucket)
        if ragged:
            run = lambda: drv(b, n, call_args, row_lens)
        else:
            run = lambda: drv(b, n, call_args)
        outs = dispatch.run_with_retries(
            run, site="launch", backend=be.name,
            family=self.name, bucket=site_bucket)
        # each output takes the shape of its template argument
        outs = [o.reshape(call_args[p].shape)
                for o, p in zip(outs, self._out_positions)]
        dispatch.record_launch(be.name)
        return outs[0] if len(outs) == 1 else tuple(outs)

    def __call__(self, *call_args, block_rows: int | None = None,
                 backend: "str | None" = None, row_lens=None):
        be = backends.get_backend(backend or self.backend)
        if row_lens is not None and self.layout != "rows":
            raise ValueError("row_lens= requires layout='rows' "
                             "(per-row masking needs the 2-D layout)")
        if self.layout == "rows":
            return self._call_rows(call_args, block_rows, be,
                                   row_lens=row_lens)
        first_vec = call_args[self._first_vec_pos]
        shape = first_vec.shape
        n = int(getattr(first_vec, "size", 0)) or int(np.prod(shape))
        br = self._pick_block_rows(n, block_rows, be.name)
        bucket = dispatch.bucket_rows(n, br)
        key = ("eltwise", be.name, self._content_key, bucket,
               br if be.block_sensitive else 0)
        drv = dispatch.get_or_build(
            key,
            lambda: be.elementwise_driver(self.spec, bucket=bucket,
                                          block_rows=br),
            backend=be.name, name=self.name, bucket=(bucket,))
        outs = [o.reshape(shape) for o in dispatch.run_with_retries(
            lambda: drv(n, call_args), site="launch", backend=be.name,
            family=self.name, bucket=(bucket,))]
        dispatch.record_launch(be.name)  # after the driver: failed launches don't count
        return outs[0] if len(outs) == 1 else tuple(outs)

    # -- tuning ------------------------------------------------------------
    def block_cost(self, params: dict, args) -> "Any":
        """Analytic `BlockCost` of one config — hybrid-mode pre-pruner."""
        from repro.core.autotune import BlockCost

        br = params["block_rows"]
        vec_bytes = sum(jnp.dtype(v.jnp_dtype).itemsize for v in self.vector_args)
        if self.layout == "rows":
            b, n = self._rows_geometry(args)
            br = dispatch.batch_block(b, br)
            brows = dispatch.bucket_batch(b, br)
            ncols = dispatch.bucket_cols(n)
            return BlockCost(
                flops=float(len(self._body_lines)) * brows * ncols,
                hbm_bytes=float(brows * ncols * vec_bytes),
                vmem_bytes=float(br * ncols * vec_bytes),
                grid=brows // br,
            )
        first = args[self._first_vec_pos]
        n = int(getattr(first, "size", 0)) or int(np.prod(first.shape))
        bucket = dispatch.bucket_rows(n, br)
        return BlockCost(
            flops=float(len(self._body_lines)) * bucket * LANES,
            hbm_bytes=float(bucket * LANES * vec_bytes),
            vmem_bytes=float(br * LANES * vec_bytes),
            grid=bucket // br,
        )

    def autotune(self, *call_args, candidates: list[dict] | None = None,
                 measure: str = "hybrid", cache=None, repeats: int = 3,
                 warmup: int = 1, prune_keep: int | None = None,
                 backend: "str | None" = None):
        """Tune ``block_rows`` for the *bucket* of these arguments.

        The winner is recorded per ``(backend, dispatch.n_bucket)``
        (flat layout) or per ``(backend, dispatch.rc_bucket)`` pair (row
        layout), so it applies to every later call whose size lands in
        the same bucket *on the same backend*, and the tuning-cache key
        uses the matching bucketed signature plus the backend name so
        results persist across exact-shape churn without leaking across
        backends.
        """
        from repro.core.autotune import batch_block_candidates, tune_per_bucket

        be = backends.get_backend(backend or self.backend)
        builder = lambda block_rows: (
            lambda *a: self(*a, block_rows=block_rows, backend=be))
        if self.layout == "rows":
            b, n = self._rows_geometry(call_args)
            return tune_per_bucket(
                f"eltwise.{self.name}", builder=builder, cost_fn=self.block_cost,
                candidates=candidates or batch_block_candidates(b),
                args=call_args, n=n, tuned=self._tuned, param="block_rows",
                measure=measure, cache=cache, repeats=repeats, warmup=warmup,
                prune_keep=prune_keep, bucket_key=dispatch.rc_bucket(b, n),
                signature_fn=dispatch.bucketed_signature_2d, backend=be.name)
        first = call_args[self._first_vec_pos]
        n = int(getattr(first, "size", 0)) or int(np.prod(first.shape))
        return tune_per_bucket(
            f"eltwise.{self.name}",
            builder=builder,
            cost_fn=self.block_cost,
            candidates=candidates or self.candidate_configs(n),
            args=call_args, n=n, tuned=self._tuned, param="block_rows",
            measure=measure, cache=cache, repeats=repeats, warmup=warmup,
            prune_keep=prune_keep, backend=be.name)

    # candidate block_rows values for the autotuner (shared pool)
    @staticmethod
    def candidate_configs(n: int) -> list[dict]:
        from repro.core.autotune import block_rows_candidates

        return block_rows_candidates(n, LANES)
