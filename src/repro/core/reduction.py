"""ReductionKernel — generated map+reduce kernels (paper §5.2).

PyCUDA's ReductionKernel takes a ``map_expr`` applied per element and a
``reduce_expr`` combining pairs, plus a neutral element.  The family
translates those snippets into a `ReductionSpec` and hands it, with a
bucketed geometry, to an execution `Backend` (`repro.core.backends`):

  * ``pallas``: grid iterations on a TensorCore execute *sequentially*,
    so a single kernel accumulates block partials into an SMEM-resident
    (1,1) output across grid steps — the canonical Pallas reduction
    idiom;
  * ``xla``: the same masked map expressions fold over the whole
    bucketed operand under ``jax.jit`` — no grid, no cross-step combine.

Either way padding lanes are masked with the neutral element against
the *runtime* element count ``_n`` (passed as a (1,1) scalar, not baked
into the source), so one compiled driver serves a whole power-of-two
shape bucket — see `repro.core.dispatch` for the bucketing math and the
shared (backend-keyed) driver LRU.

    dot = ReductionKernel(np.float32, neutral="0",
                          reduce_expr="a+b", map_expr="x[i]*y[i]",
                          arguments="float *x, float *y")

Multi-accumulator form (fusion planner `plan_many`): pass *lists* for
``dtype_out`` / ``neutral`` / ``reduce_expr`` / ``map_expr`` (equal
length) and the generated kernel evaluates every map expression over
one pass of the inputs, folding each into its own (1,1) accumulator —
sibling reductions (min/max/sum quantization stats) cost ONE launch:

    stats = ReductionKernel([np.float32] * 3, ["3.4e38", "-3.4e38", "0"],
                            ["fminf(a,b)", "fmaxf(a,b)", "a+b"],
                            ["x[i]", "x[i]", "x[i]"], "float *x")
    lo, hi, tot = stats(x)

Per-bucket autotuning: ``autotune()`` wires the shared `Autotuner`
(``signature_fn=dispatch.bucketed_signature``) to ``block_rows``, and
the winner is recorded per ``(backend, dispatch.n_bucket)`` so every
later call in the same shape bucket on the same backend uses it
automatically.

Row-segmented form (axis-aware fusion, PR 3): ``axis=-1`` reduces each
row of a ``(B, N)`` operand to its own accumulator in ONE launch —
every row lives entirely inside its block, and the runtime row length
``n`` masks padding columns with the neutral element.  Outputs are
length-B vectors.  Because a row is complete within the block, a later
accumulator's map expression may reference an earlier one as
``_acc<k>`` (a per-row value) — that is how stable softmax computes the
row max *and* the shifted-exp sum in a single launch.  Arguments may
include `BroadcastArg`s: per-row values from earlier launches bind as
``(B, 1)``, per-col weights as ``(1, N)``.  ``prelude`` lists extra
C-dialect assignment statements (hoisted common subexpressions)
evaluated once per block before the map expressions.

Column-segmented form (kernel IR, PR 7): ``axis=0`` reduces each
*column* of a ``(B, N)`` operand to a length-N vector in one launch.
The family reuses the row-segmented machinery unchanged by applying the
IR's ``transpose_layout`` transformation during lowering: the kernel
domain becomes ``(N, B)`` (every output column is a domain row), arg
kinds swap per-row <-> per-col, and the rendered driver transposes full
operands when binding — call sites keep passing storage-order data.
"""

from __future__ import annotations

import re
from typing import Any

import jax.numpy as jnp
import numpy as np

from repro.core import backends, dispatch, snippets
from repro.core.backends.base import ReductionSpec
from repro.core.cache import stable_hash
from repro.core.platform import (LANES, BroadcastArg, ScalarArg, VectorArg,
                                 arg_kind, canonical_dtype, interpret_mode,
                                 parse_arguments, rows_geometry)

# Recognized whole-block reducers (fast path); anything else raises.
_BLOCK_REDUCERS = {
    "a+b": ("jnp.sum", "+"),
    "b+a": ("jnp.sum", "+"),
    "a*b": ("jnp.prod", "*"),
    "max(a,b)": ("jnp.max", "jnp.maximum"),
    "fmaxf(a,b)": ("jnp.max", "jnp.maximum"),
    "min(a,b)": ("jnp.min", "jnp.minimum"),
    "fminf(a,b)": ("jnp.min", "jnp.minimum"),
}


class ReductionKernel:
    def __init__(self, dtype_out, neutral, reduce_expr, map_expr,
                 arguments, name: str = "reduce", preamble: str = "",
                 block_rows: int | None = None, interpret: bool | None = None,
                 axis: int | None = None, prelude=None,
                 backend: "str | None" = None):
        # Normalize the single-output and multi-accumulator forms to lists;
        # `self.multi` records which way results are handed back.
        self.multi = isinstance(map_expr, (list, tuple))
        map_exprs = list(map_expr) if self.multi else [map_expr]
        k = len(map_exprs)

        def _aslist(v):
            return list(v) if isinstance(v, (list, tuple)) else [v] * k

        neutrals, reduce_exprs = _aslist(neutral), _aslist(reduce_expr)
        dtypes_out = _aslist(dtype_out)
        if not (len(neutrals) == len(reduce_exprs) == len(dtypes_out) == k):
            raise ValueError("dtype_out/neutral/reduce_expr/map_expr lengths differ")

        self.dtypes_out = [canonical_dtype(d) for d in dtypes_out]
        self.dtype_out = self.dtypes_out[0]   # single-output compat alias
        self.neutrals = [snippets.translate_expression(nt) for nt in neutrals]
        self.neutral = self.neutrals[0]
        self.reduce_exprs = reduce_exprs
        self.reduce_expr = reduce_exprs[0]
        self.map_exprs = map_exprs
        self.map_expr = map_exprs[0]
        self.args = parse_arguments(arguments)
        self.name = re.sub(r"\W", "_", name)
        self.preamble = preamble
        self.block_rows = block_rows
        self.interpret = interpret_mode() if interpret is None else interpret
        self.backend = backend  # None: resolve REPRO_BACKEND per call
        if axis not in (None, -1, 0):
            raise NotImplementedError("only axis=None (full), axis=-1 "
                                      "(row-segmented) or axis=0 "
                                      "(column-segmented) reductions")
        self.axis = axis
        self.prelude = list(prelude or [])

        self._reducers = []
        for rexpr in reduce_exprs:
            key = re.sub(r"\s", "", rexpr)
            if key not in _BLOCK_REDUCERS:
                raise NotImplementedError(
                    f"reduce_expr {rexpr!r} not recognized; supported: {sorted(_BLOCK_REDUCERS)}")
            self._reducers.append(_BLOCK_REDUCERS[key])
        self.block_reduce, self._combine_op = self._reducers[0]
        self.scalar_args = [a for a in self.args if isinstance(a, ScalarArg)]
        self.vector_args = [a for a in self.args if isinstance(a, VectorArg)]
        self.bcast_args = [a for a in self.args if isinstance(a, BroadcastArg)]
        if self.bcast_args and self.axis is None:
            raise ValueError("BroadcastArg requires a segmented form "
                             "(axis=-1 or axis=0); a flat reduction cannot "
                             "bind per-row/per-col values")
        if not self.vector_args:
            raise ValueError("reduction needs at least one vector argument")
        names = [a.name for a in self.args]
        self._first_vec_pos = names.index(self.vector_args[0].name)
        self._arg_meta = tuple((a.name, a.jnp_dtype, arg_kind(a))
                               for a in self.args)
        self._prelude_lines = [snippets.translate_assignment(s)
                               for s in self.prelude]
        outs = self._outs()
        exprs = [o["map_expr"] for o in outs] + self._prelude_lines
        loaded = sorted({v.name for v in (self.vector_args + self.bcast_args)
                         if any(re.search(rf"\b{re.escape(v.name)}\b", e)
                                for e in exprs)})
        self.spec = ReductionSpec(
            name=self.name,
            arg_meta=self._arg_meta,
            scalar_names=tuple(s.name for s in self.scalar_args),
            loaded_vectors=tuple(loaded),
            prelude_lines=tuple(self._prelude_lines),
            outs=tuple(outs),
            multi=self.multi,
            axis=self.axis,
            preamble=self.preamble,
            interpret=self.interpret,
        )
        self._content_key = stable_hash(self.spec.token())
        self._tuned: dict = {}      # (backend, bucket key) -> tuned block_rows

    def _outs(self) -> list[dict]:
        outs = []
        for j, (mapped, nt, (block_reduce, op)) in enumerate(
                zip(self.map_exprs, self.neutrals, self._reducers)):
            combine = (f"_prev{j} {op} _partial{j}" if op in ("+", "*")
                       else f"{op}(_prev{j}, _partial{j})")
            outs.append({
                "map_expr": snippets.translate_expression(mapped),
                "neutral": nt,
                "block_reduce": block_reduce,
                "combine": combine,
                "dtype": str(self.dtypes_out[j]),
            })
        return outs

    def render(self, block_rows: int, ncols: int | None = None,
               backend: "str | None" = None) -> str:
        """Source this kernel's spec renders to on ``backend``."""
        return backends.get_backend(backend or self.backend).render_reduction(
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
                or autotune.sequence_param(f"reduce.{self.name}", be_name,
                                           bucket, "block_rows")
                or self.block_rows or dispatch.default_block_rows(n))

    def _rows_geometry(self, call_args) -> tuple[int, int]:
        return rows_geometry(call_args[self._first_vec_pos])

    def _domain_geometry(self, call_args) -> tuple[int, int]:
        """Kernel-domain (rows, cols) counts.  axis=-1 reduces each
        storage row, so the domain is the storage geometry; axis=0
        reduces each storage *column*, so `transpose_layout` makes every
        output column a domain row — (B, N) storage becomes an (N, B)
        domain.  Operands still travel in storage order; the rendered
        driver transposes full operands when binding."""
        b, n = self._rows_geometry(call_args)
        return (n, b) if self.axis == 0 else (b, n)

    def _call_rows(self, call_args, block_rows: int | None, be,
                   row_lens=None):
        from repro.core import autotune
        ragged = row_lens is not None
        tb, tn = self._domain_geometry(call_args)
        bucket = dispatch.rc_bucket(tb, tn, transposed=(self.axis == 0),
                                    ragged=ragged)
        br = dispatch.batch_block(tb, (
            block_rows or self._tuned.get((be.name, bucket))
            or autotune.sequence_param(f"reduce.{self.name}", be.name,
                                       bucket, "block_rows")
            or self.block_rows or dispatch.default_batch_block(tb)))
        brows = dispatch.bucket_batch(tb, br)
        ncols = dispatch.bucket_cols(tn)
        key = ("reduce_rows", be.name, self._content_key, brows, ncols,
               br if be.block_sensitive else 0)
        site_bucket = (brows, ncols, "R") if ragged else (brows, ncols)
        if ragged:
            key = key + ("R",)   # dense keys stay byte-identical
        drv = dispatch.get_or_build(
            key,
            lambda: be.reduction_rows_driver(self.spec, brows=brows,
                                             ncols=ncols, block_rows=br,
                                             ragged=ragged),
            backend=be.name, name=self.name, bucket=site_bucket)
        if ragged:
            run = lambda: drv(tb, tn, call_args, row_lens)
        else:
            run = lambda: drv(tb, tn, call_args)
        out = dispatch.run_with_retries(
            run, site="launch", backend=be.name,
            family=self.name, bucket=site_bucket)
        dispatch.record_launch(be.name)
        return out

    def __call__(self, *call_args, block_rows: int | None = None,
                 backend: "str | None" = None, row_lens=None):
        be = backends.get_backend(backend or self.backend)
        if row_lens is not None and self.axis is None:
            raise ValueError("row_lens requires the row-segmented form "
                             "(axis=-1)")
        if self.axis is not None:
            return self._call_rows(call_args, block_rows, be,
                                   row_lens=row_lens)
        first_vec = call_args[self._first_vec_pos]
        n = int(getattr(first_vec, "size", 0)) or int(np.prod(first_vec.shape))
        br = self._pick_block_rows(n, block_rows, be.name)
        bucket = dispatch.bucket_rows(n, br)
        key = ("reduce", be.name, self._content_key, bucket,
               br if be.block_sensitive else 0)
        drv = dispatch.get_or_build(
            key,
            lambda: be.reduction_driver(self.spec, bucket=bucket,
                                        block_rows=br),
            backend=be.name, name=self.name, bucket=(bucket,))
        out = dispatch.run_with_retries(
            lambda: drv(n, call_args), site="launch", backend=be.name,
            family=self.name, bucket=(bucket,))
        dispatch.record_launch(be.name)  # after the driver: failed launches don't count
        return out

    # -- tuning ------------------------------------------------------------
    def block_cost(self, params: dict, args) -> "Any":
        """Analytic `BlockCost` of one config — hybrid-mode pre-pruner."""
        from repro.core.autotune import BlockCost

        br = params["block_rows"]
        vec_bytes = sum(jnp.dtype(v.jnp_dtype).itemsize for v in self.vector_args)
        if self.axis is not None:
            b, n = self._domain_geometry(args)
            br = dispatch.batch_block(b, br)
            brows = dispatch.bucket_batch(b, br)
            ncols = dispatch.bucket_cols(n)
            return BlockCost(
                flops=float(2 * len(self.map_exprs)) * brows * ncols,
                hbm_bytes=float(brows * ncols * vec_bytes),
                vmem_bytes=float(br * ncols * vec_bytes),
                grid=brows // br,
            )
        first = args[self._first_vec_pos]
        n = int(getattr(first, "size", 0)) or int(np.prod(first.shape))
        bucket = dispatch.bucket_rows(n, br)
        return BlockCost(
            flops=float(2 * len(self.map_exprs)) * bucket * LANES,
            hbm_bytes=float(bucket * LANES * vec_bytes),
            vmem_bytes=float(br * LANES * vec_bytes),
            grid=bucket // br,
        )

    def autotune(self, *call_args, candidates: list[dict] | None = None,
                 measure: str = "hybrid", cache=None, repeats: int = 3,
                 warmup: int = 1, prune_keep: int | None = None,
                 backend: "str | None" = None):
        """Tune ``block_rows`` for the *bucket* of these arguments.

        Same contract as `ElementwiseKernel.autotune`: the winner is
        recorded per ``(backend, dispatch.n_bucket)`` (flat) or
        ``(backend, dispatch.rc_bucket)`` pair (row-segmented), so one
        tuning run covers every shape in the bucket on that backend.
        """
        from repro.core.autotune import (batch_block_candidates,
                                         block_rows_candidates, tune_per_bucket)

        be = backends.get_backend(backend or self.backend)
        builder = lambda block_rows: (
            lambda *a: self(*a, block_rows=block_rows, backend=be))
        if self.axis is not None:
            tb, tn = self._domain_geometry(call_args)
            return tune_per_bucket(
                f"reduce.{self.name}", builder=builder, cost_fn=self.block_cost,
                candidates=candidates or batch_block_candidates(tb),
                args=call_args, n=tn, tuned=self._tuned, param="block_rows",
                measure=measure, cache=cache, repeats=repeats, warmup=warmup,
                prune_keep=prune_keep,
                bucket_key=dispatch.rc_bucket(tb, tn,
                                              transposed=(self.axis == 0)),
                signature_fn=dispatch.bucketed_signature_2d, backend=be.name)
        first = call_args[self._first_vec_pos]
        n = int(getattr(first, "size", 0)) or int(np.prod(first.shape))
        return tune_per_bucket(
            f"reduce.{self.name}",
            builder=builder,
            cost_fn=self.block_cost,
            candidates=candidates or block_rows_candidates(n),
            args=call_args, n=n, tuned=self._tuned, param="block_rows",
            measure=measure, cache=cache, repeats=repeats, warmup=warmup,
            prune_keep=prune_keep, backend=be.name)
