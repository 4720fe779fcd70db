"""PallasBackend — the TPU execution target (pallas_call assembly).

This is the launch path the kernel families used to hand-assemble
themselves: render the transformed kernel IR into *Pallas kernel
source* (refs, block specs, a sequential 1-D grid), ``SourceModule.load``
it (content addressed — identical renders compile once), wrap in
``pl.pallas_call`` + ``jax.jit``, and return a driver that pads
operands to the bucketed block shape on the way in and slices/masks on
the way out.  The IR's tiled ``rows`` axis IS the grid: block shape
``(rows.block, lanes)``, grid length ``extent // block``; a
``transpose_layout`` entry makes the segmented-reduction driver bind
full operands transposed (axis=0 column reductions).

TPU realization notes (see the repo's Pallas idioms):

  * elementwise: ``(rows, LANES)`` lane layout, ``block_rows``-row VMEM
    blocks, 1-D grid;
  * flat reduction: grid steps on a TensorCore run *sequentially*, so
    block partials accumulate into a (1, 1) output across steps;
  * row reduction: the grid runs over row blocks; each row reduces
    entirely inside its block (no cross-step combine), later
    accumulators may reference earlier ones (``_acc<k>``);
  * scan: two generated passes (per-block inclusive scan + carry add)
    around a tiny host combine over block totals.

``interpret`` (from the spec) selects Pallas interpreter mode off-TPU.
"""

from __future__ import annotations

import re
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.backends.base import Backend, bind_row_operand, binop_apply
from repro.core.platform import (LANES, interpret_mode, pad_flat_operand,
                                 pad_row_operand)
from repro.core.templates import KernelTemplate


def row_block_specs(block_rows: int, ncols: int) -> dict:
    """BlockSpec per operand kind for a (brows, ncols) row layout."""
    return {
        "scalar": pl.BlockSpec((1, 1), lambda r: (0, 0)),
        "full": pl.BlockSpec((block_rows, ncols), lambda r: (r, 0)),
        "row": pl.BlockSpec((block_rows, 1), lambda r: (r, 0)),
        "col": pl.BlockSpec((1, ncols), lambda r: (0, 0)),
    }


_ELTWISE_TMPL = KernelTemplate(
    "eltwise",
    '''
def {{ name }}_kernel({% if ragged %}_n_ref, {% endif %}{% for a in in_names %}{{ a }}_ref, {% endfor %}{% for o in out_names %}{{ o }}_out_ref{{ ", " if not loop.last }}{% endfor %}):
{% for s in scalar_names %}
    {{ s }} = {{ s }}_ref[0, 0]
{% endfor %}
{% if needs_i %}
    _row = jax.lax.broadcasted_iota(jnp.int32, ({{ block_rows }}, {{ lanes }}), 0)
    _col = jax.lax.broadcasted_iota(jnp.int32, ({{ block_rows }}, {{ lanes }}), 1)
    i = (pl.program_id(0) * {{ block_rows }} + _row) * {{ lanes }} + _col
{% endif %}
{% if ragged %}
    _n = _n_ref[...]
    _rcol = jax.lax.broadcasted_iota(jnp.int32, ({{ block_rows }}, {{ lanes }}), 1)
{% endif %}
    _BLK = ({{ block_rows }}, {{ lanes }})
{% for v in loaded_vectors %}
    {{ v }} = {{ v }}_ref[...]
{% endfor %}
{% for line in body_lines %}
    {{ line }}
{% endfor %}
{% for o in out_names %}
{% if ragged %}
    {{ o }}_out_ref[...] = jnp.where(_rcol < _n, {{ o }}, jnp.zeros_like({{ o }}))
{% else %}
    {{ o }}_out_ref[...] = {{ o }}
{% endif %}
{% endfor %}
''',
)

_REDUCE_TMPL = KernelTemplate(
    "reduction",
    '''
def {{ name }}_kernel(_n_ref, {% for a in in_names %}{{ a }}_ref, {% endfor %}{% for o in outs %}o{{ loop.index0 }}_ref{{ ", " if not loop.last }}{% endfor %}):
    _n = _n_ref[0, 0]
{% for s in scalar_names %}
    {{ s }} = {{ s }}_ref[0, 0]
{% endfor %}
    _row = jax.lax.broadcasted_iota(jnp.int32, ({{ block_rows }}, {{ lanes }}), 0)
    _col = jax.lax.broadcasted_iota(jnp.int32, ({{ block_rows }}, {{ lanes }}), 1)
    i = (pl.program_id(0) * {{ block_rows }} + _row) * {{ lanes }} + _col
{% for v in loaded_vectors %}
    {{ v }} = {{ v }}_ref[...]
{% endfor %}
{% for line in prelude_lines %}
    {{ line }}
{% endfor %}
{% for o in outs %}
    _mapped{{ loop.index0 }} = jnp.asarray({{ o.map_expr }}).astype(jnp.{{ o.dtype }})
    _mapped{{ loop.index0 }} = jnp.where(i < _n, _mapped{{ loop.index0 }}, jnp.asarray({{ o.neutral }}, jnp.{{ o.dtype }}))
    _partial{{ loop.index0 }} = {{ o.block_reduce }}(_mapped{{ loop.index0 }}, keepdims=True)
    _prev{{ loop.index0 }} = jnp.where(pl.program_id(0) == 0,
                                       jnp.full((1, 1), {{ o.neutral }}, jnp.{{ o.dtype }}),
                                       o{{ loop.index0 }}_ref[...])
    o{{ loop.index0 }}_ref[...] = {{ o.combine }}
{% endfor %}
''',
)

# Row-segmented form: the grid runs over blocks of *rows* of a (B, N)
# operand; each row reduces inside its block (no cross-step combine), the
# runtime row length masks padding columns, and later accumulators may
# reference earlier ones (`_acc<k>`, a per-row (block, 1) value).
_ROW_REDUCE_TMPL = KernelTemplate(
    "row_reduction",
    '''
def {{ name }}_kernel(_n_ref, {% for a in in_names %}{{ a }}_ref, {% endfor %}{% for o in outs %}o{{ loop.index0 }}_ref{{ ", " if not loop.last }}{% endfor %}):
{% if ragged %}
    _n = _n_ref[...]
{% else %}
    _n = _n_ref[0, 0]
{% endif %}
{% for s in scalar_names %}
    {{ s }} = {{ s }}_ref[0, 0]
{% endfor %}
    _col = jax.lax.broadcasted_iota(jnp.int32, ({{ block_rows }}, {{ ncols }}), 1)
{% for v in loaded_vectors %}
    {{ v }} = {{ v }}_ref[...]
{% endfor %}
{% for line in prelude_lines %}
    {{ line }}
{% endfor %}
{% for o in outs %}
    _mapped{{ loop.index0 }} = jnp.asarray({{ o.map_expr }}).astype(jnp.{{ o.dtype }})
    _mapped{{ loop.index0 }} = jnp.where(_col < _n, _mapped{{ loop.index0 }}, jnp.asarray({{ o.neutral }}, jnp.{{ o.dtype }}))
    _acc{{ loop.index0 }} = {{ o.block_reduce }}(_mapped{{ loop.index0 }}, axis=1, keepdims=True)
    o{{ loop.index0 }}_ref[...] = _acc{{ loop.index0 }}
{% endfor %}
''',
)

# Two-pass blocked scan over (1, block_n) rows.  Mosaic has no lowering
# for cumsum/cummax, so the in-block scan is `lane_scan`'s log-step roll
# form; the block total is the scan's last lane, rolled to lane 0.
_SCAN1_TMPL = KernelTemplate(
    "scan1",
    '''
def {{ name }}(x_ref, y_ref, tot_ref):
    s = lane_scan(x_ref[...].astype(jnp.{{ dtype }}), {{ combine_fn }},
                  jnp.asarray({{ neutral }}, jnp.{{ dtype }}))
    y_ref[...] = s
    tot_ref[...] = pltpu.roll(s, 1, 1)[:, :1]
''',
)

_SCAN2_TMPL = KernelTemplate(
    "scan2",
    '''
def {{ name }}(y_ref, off_ref, o_ref):
    off = off_ref[...]
{% if exclusive %}
    # exclusive: shift right by one within the global stream; the driver
    # passes the per-block carry already exclusive of this block.
    y = y_ref[...]
    _lane = jax.lax.broadcasted_iota(jnp.int32, y.shape, 1)
    o_ref[...] = jnp.where(_lane == 0, off, pltpu.roll({{ binop_expr }}, 1, 1))
{% else %}
    o_ref[...] = {{ combine }}
{% endif %}
''',
)

_COMBINE_FNS = {"+": "jnp.add", "*": "jnp.multiply"}


def lane_scan(v, combine, neutral):
    """Inclusive scan of ``v`` along its last (lane) axis in
    ``log2(width)`` rotate-and-combine steps (Hillis-Steele).  Kernel
    library function: Pallas TPU has no lowering for ``cumsum``."""
    from jax.experimental.pallas import tpu as pltpu

    axis = v.ndim - 1
    lane = jax.lax.broadcasted_iota(jnp.int32, v.shape, axis)
    k = 1
    while k < v.shape[axis]:
        v = combine(v, jnp.where(lane >= k, pltpu.roll(v, k, axis), neutral))
        k *= 2
    return v


# Names generated Pallas source links against beyond the default namespace.
_KERNEL_LIB = {"lane_scan": lane_scan}

# An output line `ElementwiseKernel` renders for ``out[i] = cumsumf(e)``.
_ROW_SCAN_RE = re.compile(
    r"^(?P<tgt>\w+) = jnp\.broadcast_to\(jnp\.asarray\(cumsum_lanes\("
    r"(?P<arg>.*)\)\), _BLK\)(?P<cast>\.astype\(jnp\.\w+\))$")


def _wraps_whole(arg: str) -> bool:
    """Whether ``cumsum_lanes(<arg>)`` is one call around all of ``arg``
    (its parentheses never close the call early)."""
    depth = 0
    for ch in arg:
        depth += (ch == "(") - (ch == ")")
        if depth < 0:
            return False
    return depth == 0


def split_row_scans(lines, out_names) -> tuple:
    """Pallas TPU has no ``cumsum`` lowering, and an in-kernel log-step
    scan over a 131072-lane row takes Mosaic over a minute to compile.  So an
    output assigned ``cumsumf(e)`` as a whole (the sampler's inverse-CDF
    epilogue) keeps ``e`` in the kernel, and the driver prefix-sums that
    output along its row with XLA in the same jitted program: still one
    launch.  Returns (kernel body lines, names of the outputs to scan);
    any other use of ``cumsumf`` raises."""
    body, scans = [], []
    for ln in lines:
        if any(re.search(rf"\b{t}\b", ln) for t in scans):
            raise NotImplementedError(
                f"pallas: output {scans} is read or rewritten after its "
                "cumsumf() assignment")
        m = _ROW_SCAN_RE.match(ln)
        if m and m["tgt"] in out_names and _wraps_whole(m["arg"]):
            body.append(f"{m['tgt']} = jnp.broadcast_to("
                        f"jnp.asarray({m['arg']}), _BLK){m['cast']}")
            scans.append(m["tgt"])
        elif "cumsum_lanes" in ln:
            raise NotImplementedError(
                "pallas: cumsumf() must be the whole right-hand side of a "
                f"row-layout output assignment, got {ln!r}")
        else:
            body.append(ln)
    return body, scans


def _load(src: str, name: "str | None" = None):
    from repro.core.rtcg import SourceModule

    return SourceModule.load(src, namespace=_KERNEL_LIB, name=name)


def _interpret(kir) -> bool:
    """The spec's resolved interpret flag; an IR without one is a bug
    (a chip run must never fall into the interpreter by default)."""
    flag = kir.meta_get("interpret")
    if flag is None:
        raise ValueError(f"kernel IR {kir.name!r} carries no interpret flag")
    return bool(flag)


#: Scoped-VMEM ceiling for one generated kernel.  A TPU v5e core has
#: 128 MiB of VMEM; the rest is headroom for Mosaic's internal scratch.
VMEM_LIMIT_CAP = 100 << 20


def _compiler_params(interpret: bool, tile_elems: int, n_tiles: int):
    """Mosaic parameters for one generated kernel: a scoped-VMEM limit
    sized to its blocks — ``n_tiles`` block-sized operands, each double
    buffered, plus room for block-sized f32 temporaries — instead of
    the 16 MiB default that a few 8-row blocks of a 131072-column row
    already exceed."""
    if interpret:
        return None
    from jax.experimental.pallas import tpu as pltpu

    tile = 4 * int(tile_elems)
    limit = min(VMEM_LIMIT_CAP, max(32 << 20, (2 * n_tiles + 8) * tile))
    return pltpu.CompilerParams(vmem_limit_bytes=limit)


def _with_preamble(preamble: str, src: str) -> str:
    return (preamble + "\n" + src) if preamble else src


class PallasBackend(Backend):
    name = "pallas"

    def fingerprint(self) -> dict:
        return {
            "backend": self.name,
            "target": "interpret" if interpret_mode() else "tpu",
            "jax": jax.__version__,
        }

    # -- render (IR -> pallas kernel source) -----------------------------
    def render_ir(self, kir):
        """The tiled parallel/sequential ``rows`` axis becomes the 1-D
        grid: the template's block shape is ``(rows.block, <lane axis
        extent>)`` and the grid steps ``extent // block`` tiles."""
        if kir.kind == "elementwise":
            rows = kir.axis("rows")
            lane_ax = kir.axes[1]
            out_names = [o[0] for o in kir.outs]
            body = split_row_scans(kir.lines("body"), out_names)[0]
            src = _ELTWISE_TMPL.render(
                name=kir.name,
                in_names=[a[0] for a in kir.args],
                out_names=out_names,
                scalar_names=list(kir.meta_get("scalar_names", ())),
                loaded_vectors=list(kir.meta_get("loaded_vectors", ())),
                body_lines=body,
                needs_i=kir.meta_get("needs_i", False),
                ragged=kir.meta_get("ragged", False),
                block_rows=rows.block or rows.extent,
                lanes=lane_ax.extent,
            )
            return _with_preamble(kir.meta_get("preamble", ""), src)
        if kir.kind == "reduction":
            rows = kir.axis("rows")
            tmpl_kwargs = dict(
                name=kir.name,
                in_names=[a[0] for a in kir.args],
                scalar_names=list(kir.meta_get("scalar_names", ())),
                loaded_vectors=list(kir.meta_get("loaded_vectors", ())),
                prelude_lines=kir.lines("prelude"),
                outs=list(kir.outs),
                block_rows=rows.block or rows.extent,
            )
            if kir.meta_get("layout") == "flat":
                src = _REDUCE_TMPL.render(lanes=kir.axis("lanes").extent,
                                          **tmpl_kwargs)
            else:
                src = _ROW_REDUCE_TMPL.render(ncols=kir.axis("cols").extent,
                                              ragged=kir.meta_get("ragged",
                                                                  False),
                                              **tmpl_kwargs)
            return _with_preamble(kir.meta_get("preamble", ""), src)
        if kir.kind == "scan":
            binop = kir.meta_get("binop")
            src1 = _SCAN1_TMPL.render(
                name=f"{kir.name}_p1", dtype=kir.meta_get("dtype"),
                neutral=kir.meta_get("neutral"),
                combine_fn=_COMBINE_FNS.get(binop, binop))
            src2 = _SCAN2_TMPL.render(
                name=f"{kir.name}_p2", exclusive=kir.meta_get("exclusive"),
                binop_expr=binop_apply(binop, "y", "off"),
                combine=binop_apply(binop, "y_ref[...]", "off"))
            return src1, src2
        raise ValueError(f"unknown IR kind {kir.kind!r}")

    # -- elementwise -----------------------------------------------------
    def build_elementwise(self, kir) -> Callable:
        """The pallas_call is traced once over the static ``(bucket,
        LANES)`` shape; the element count only appears at run time
        (padding on the way in, slicing on the way out), so the driver
        is reused across the whole bucket."""
        bucket = kir.axis("rows").extent
        block_rows = kir.axis("rows").block
        lanes = kir.axis("lanes").extent
        grid = bucket // block_rows
        interpret = _interpret(kir)
        if split_row_scans(kir.lines("body"), [o for o, _ in kir.outs])[1]:
            raise NotImplementedError("pallas: cumsumf() needs layout='rows'")
        kernel = _load(self.render_ir(kir), kir.name).get_function(
            f"{kir.name}_kernel")

        blk = pl.BlockSpec((block_rows, lanes), lambda r: (r, 0))
        scl = pl.BlockSpec((1, 1), lambda r: (0, 0))
        in_specs = [scl if kind == "scalar" else blk
                    for _, _, kind in kir.args]
        out_shape = [jax.ShapeDtypeStruct((bucket, lanes), jnp.dtype(d))
                     for _, d in kir.outs]
        call = jax.jit(pl.pallas_call(
            kernel,
            grid=(grid,),
            in_specs=in_specs,
            out_specs=[blk] * len(kir.outs),
            out_shape=out_shape,
            compiler_params=_compiler_params(
                interpret, block_rows * lanes, len(in_specs) + len(out_shape)),
            interpret=interpret,
        ))
        arg_meta = [(n, jnp.dtype(d), k) for n, d, k in kir.args]

        def driver(n, flat_args):
            padded = [pad_flat_operand(kind, name, arg, dt, n, bucket, lanes)
                      for (name, dt, kind), arg in zip(arg_meta, flat_args)]
            outs = call(*padded)
            return [o.reshape(-1)[:n] for o in outs]

        return _expose(driver, call, interpret)

    def build_elementwise_rows(self, kir) -> Callable:
        """One driver per (source, batch-bucket, row-length-bucket): blocks
        are ``(block_rows, ncols)`` row groups, per-row broadcast args bind
        as ``(block_rows, 1)``, per-col as ``(1, ncols)``.  Row padding is
        sliced off on the way out, so any ``(B, N)`` whose buckets match
        reuses this compile."""
        brows = kir.axis("rows").extent
        block_rows = kir.axis("rows").block
        ncols = kir.axis("lanes").extent
        grid = brows // block_rows
        interpret = _interpret(kir)
        kernel = _load(self.render_ir(kir), kir.name).get_function(
            f"{kir.name}_kernel")

        spec_map = row_block_specs(block_rows, ncols)
        ragged = bool(kir.meta_get("ragged", False))
        in_specs = ([spec_map["row"]] if ragged else []) + \
            [spec_map[kind] for _, _, kind in kir.args]
        out_shape = [jax.ShapeDtypeStruct((brows, ncols), jnp.dtype(d))
                     for _, d in kir.outs]
        kernel_call = pl.pallas_call(
            kernel,
            grid=(grid,),
            in_specs=in_specs,
            out_specs=[spec_map["full"]] * len(kir.outs),
            out_shape=out_shape,
            compiler_params=_compiler_params(
                interpret, block_rows * ncols,
                sum(k == "full" for _, _, k in kir.args) + len(out_shape)),
            interpret=interpret,
        )
        scans = split_row_scans(kir.lines("body"), [o for o, _ in kir.outs])[1]
        # a row prefix sum is causal: padding columns past a row's
        # length never reach the sliced-out [:n]
        scan_out = [o in scans for o, _ in kir.outs]

        @jax.jit
        def call(*operands):
            outs = kernel_call(*operands)
            return [jnp.cumsum(o, axis=1) if s else o
                    for o, s in zip(outs, scan_out)]

        arg_meta = [(n, jnp.dtype(d), k) for n, d, k in kir.args]

        def driver(b, n, flat_args, row_lens=None):
            padded = []
            if ragged:
                lens = jnp.asarray(row_lens, jnp.int32).reshape(-1)
                padded.append(pad_row_operand("row", "_n", lens, jnp.int32,
                                              b, n, brows, ncols))
            padded += [bind_row_operand(kind, name, arg, dt, b, n, brows,
                                        ncols)
                       for (name, dt, kind), arg in zip(arg_meta, flat_args)]
            outs = call(*padded)
            return [o[:b, :n] for o in outs]

        return _expose(driver, call, interpret)

    # -- reduction -------------------------------------------------------
    def build_reduction(self, kir) -> Callable:
        """One driver per (source, bucket): the element count is a runtime
        scalar feeding the in-kernel neutral mask, so any ``n`` whose
        padded rows fit the bucket reuses this compile."""
        bucket = kir.axis("rows").extent
        block_rows = kir.axis("rows").block
        lanes = kir.axis("lanes").extent
        grid = bucket // block_rows
        interpret = _interpret(kir)
        kernel = _load(self.render_ir(kir), kir.name).get_function(
            f"{kir.name}_kernel")

        blk = pl.BlockSpec((block_rows, lanes), lambda r: (r, 0))
        scl = pl.BlockSpec((1, 1), lambda r: (0, 0))
        in_specs = [scl] + [scl if kind == "scalar" else blk
                            for _, _, kind in kir.args]
        call = jax.jit(pl.pallas_call(
            kernel,
            grid=(grid,),
            in_specs=in_specs,
            out_specs=[pl.BlockSpec((1, 1), lambda r: (0, 0))] * len(kir.outs),
            out_shape=[jax.ShapeDtypeStruct((1, 1), jnp.dtype(o["dtype"]))
                       for o in kir.outs],
            compiler_params=_compiler_params(
                interpret, block_rows * lanes, len(in_specs)),
            interpret=interpret,
        ))
        arg_meta = [(n, jnp.dtype(d), k) for n, d, k in kir.args]
        multi = kir.meta_get("multi", False)

        def driver(n, flat_args):
            padded = [jnp.full((1, 1), n, dtype=jnp.int32)]
            padded += [pad_flat_operand(kind, name, arg, dt, n, bucket, lanes)
                       for (name, dt, kind), arg in zip(arg_meta, flat_args)]
            outs = call(*padded)
            if multi:
                return tuple(o[0, 0] for o in outs)
            return outs[0][0, 0]

        return _expose(driver, call, interpret)

    def build_reduction_rows(self, kir) -> Callable:
        """Segmented driver: one accumulator per domain row, single
        launch.  The runtime length ``n`` masks padding columns; padded
        *rows* compute on zeros and are sliced off the (b,)-shaped
        outputs.  ``kir.transposed`` (axis=0 column reductions) binds
        full operands transposed into domain order."""
        brows = kir.axis("rows").extent
        block_rows = kir.axis("rows").block
        ncols = kir.axis("cols").extent
        grid = brows // block_rows
        interpret = _interpret(kir)
        kernel = _load(self.render_ir(kir), kir.name).get_function(
            f"{kir.name}_kernel")

        spec_map = row_block_specs(block_rows, ncols)
        ragged = bool(kir.meta_get("ragged", False))
        in_specs = [spec_map["row" if ragged else "scalar"]] + \
            [spec_map[kind] for _, _, kind in kir.args]
        call = jax.jit(pl.pallas_call(
            kernel,
            grid=(grid,),
            in_specs=in_specs,
            out_specs=[spec_map["row"]] * len(kir.outs),
            out_shape=[jax.ShapeDtypeStruct((brows, 1), jnp.dtype(o["dtype"]))
                       for o in kir.outs],
            compiler_params=_compiler_params(
                interpret, block_rows * ncols,
                sum(k == "full" for _, _, k in kir.args)),
            interpret=interpret,
        ))
        arg_meta = [(n, jnp.dtype(d), k) for n, d, k in kir.args]
        multi = kir.meta_get("multi", False)
        transposed = kir.transposed

        def driver(b, n, flat_args, row_lens=None):
            if ragged:
                lens = jnp.asarray(row_lens, jnp.int32).reshape(-1)
                # padded rows bind length 0 -> fully neutral-masked
                padded = [pad_row_operand("row", "_n", lens, jnp.int32,
                                          b, n, brows, ncols)]
            else:
                padded = [jnp.full((1, 1), n, dtype=jnp.int32)]
            padded += [bind_row_operand(kind, name, arg, dt, b, n, brows,
                                        ncols, transposed)
                       for (name, dt, kind), arg in zip(arg_meta, flat_args)]
            outs = call(*padded)
            if multi:
                return tuple(o[:b, 0] for o in outs)
            return outs[0][:b, 0]

        return _expose(driver, call, interpret)

    # -- scan ------------------------------------------------------------
    def build_scan(self, kir) -> Callable:
        """One driver per (source, grid bucket, block_n): padding with the
        neutral element makes the tail blocks no-ops, so any ``n`` needing
        at most ``grid`` blocks reuses this compile.  The stream is laid
        out ``(grid, 1, block_n)`` so each squeezed ``(1, block_n)``
        block spans its array's last two dims (the TPU tiling rule)."""
        grid = kir.axis("stream.o").extent
        bn = kir.axis("stream.i").extent
        pn = grid * bn
        dt = jnp.dtype(kir.meta_get("dtype"))
        interpret = _interpret(kir)

        src1, src2 = self.render_ir(kir)
        k1 = _load(src1).get_function(f"{kir.name}_p1")
        k2 = _load(src2).get_function(f"{kir.name}_p2")

        row = pl.BlockSpec((None, 1, bn), lambda i: (i, 0, 0))
        one = pl.BlockSpec((None, 1, 1), lambda i: (i, 0, 0))
        p1 = pl.pallas_call(
            k1, grid=(grid,), in_specs=[row], out_specs=[row, one],
            out_shape=[jax.ShapeDtypeStruct((grid, 1, bn), dt),
                       jax.ShapeDtypeStruct((grid, 1, 1), dt)],
            interpret=interpret)
        p2 = pl.pallas_call(
            k2, grid=(grid,), in_specs=[row, one], out_specs=row,
            out_shape=jax.ShapeDtypeStruct((grid, 1, bn), dt),
            interpret=interpret)

        neutral = kir.meta_get("neutral")
        binop = kir.meta_get("binop")

        @jax.jit
        def core(xp):
            partial, totals = p1(xp)
            totals = totals[:, 0, 0]
            # tiny exclusive combine over block totals
            if binop == "+":
                carry = jnp.cumsum(totals) - totals
                carry = carry + jnp.asarray(neutral, dt)
            elif binop == "*":
                # exclusive product via shift, NOT cumprod/totals division
                # (a zero block total would make that 0/0 = NaN)
                shifted = jnp.concatenate(
                    [jnp.full((1,), np.asarray(neutral, dt)), totals[:-1]])
                carry = jnp.cumprod(shifted)
            else:
                fn = jax.lax.cummax if "max" in binop else jax.lax.cummin
                shifted = jnp.concatenate(
                    [jnp.full((1,), np.asarray(neutral, dt)), totals[:-1]])
                carry = fn(shifted)
            return p2(partial, carry[:, None, None])

        def driver(n, x):
            xf = jnp.ravel(jnp.asarray(x)).astype(dt)
            if int(xf.size) != pn:
                xp = jnp.pad(xf, (0, pn - int(xf.size)),
                             constant_values=np.asarray(neutral, dt))
            else:
                xp = xf
            out = core(xp.reshape(grid, 1, bn))
            return out.reshape(-1)[:n]

        return _expose(driver, core, interpret)


def _expose(driver: Callable, call: Callable, interpret: bool) -> Callable:
    """Attach the driver's jitted program (``driver.call``, taking the
    padded operands) and its resolved ``interpret`` flag, so a test can
    compile the program for a described chip and a run can prove that
    no driver it built interprets."""
    driver.call = call
    driver.interpret = interpret
    return driver
