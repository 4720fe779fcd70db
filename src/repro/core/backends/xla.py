"""XlaBackend — pure-XLA execution target (no Pallas dependency).

The paper's "second toolkit": the same kernel IR the Pallas backend
tiles into VMEM blocks renders here to plain ``jnp`` operations over
whole (bucketed) operands, compiled by ``jax.jit`` — masked segment
reductions instead of grid-step accumulators, broadcast epilogues
instead of BlockSpec binding, associative host-free scans instead of
the two-pass blocked scan.  Tiled axes are ignored (there is no grid);
only the IR's axis *extents* shape the code, which is why this backend
is ``block_sensitive = False``.  A ``transpose_layout`` entry is
honored the same way as on pallas: bind full operands transposed.  PyCUDA vs PyOpenCL in miniature:
everything upstream of ``render`` (snippet translation, fusion
planning, bucketing math, caching, autotuning) is shared verbatim;
only the compile-and-launch step differs.

Semantics contract with `PallasBackend` (asserted by the fusion test
suites, which run against both):

  * identical driver calling conventions and launch counting — one
    driver call is one launch, whatever XLA fuses internally;
  * identical bucketing: operands are padded to the same bucketed
    shapes so a size sweep compiles the same log-many drivers and the
    runtime ``n`` masks (reductions) or slices (elementwise) the same
    way — padding must never hide a size bug on either backend;
  * allclose numerics (reduction order differs: whole-array folds here
    vs sequential block accumulation there).

Generated source still goes through `SourceModule.load`, so the XLA
target keeps the paper's workflow — source text in, cached callable
out — and generated code stays introspectable in tracebacks.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.backends.base import Backend, bind_row_operand, binop_apply
from repro.core.platform import LANES, pad_flat_operand, pad_row_operand
from repro.core.templates import KernelTemplate

# The XLA lowering of an elementwise spec: one function over the whole
# padded (rows, lanes) operand block.  Parameters are the *bare* operand
# names (no refs), so the same translated body lines run unchanged; the
# global element index `i` is a full-shape iota instead of a
# program_id-offset block iota.
_ELTWISE_TMPL = KernelTemplate(
    "xla_eltwise",
    '''
def {{ name }}_fn({% if ragged %}_n_ref, {% endif %}{% for a in in_names %}{{ a }}{{ ", " if not loop.last }}{% endfor %}):
{% for s in scalar_names %}
    {{ s }} = {{ s }}[0, 0]
{% endfor %}
{% if needs_i %}
    _row = jax.lax.broadcasted_iota(jnp.int32, ({{ rows }}, {{ lanes }}), 0)
    _col = jax.lax.broadcasted_iota(jnp.int32, ({{ rows }}, {{ lanes }}), 1)
    i = _row * {{ lanes }} + _col
{% endif %}
{% if ragged %}
    _n = _n_ref
    _rcol = jax.lax.broadcasted_iota(jnp.int32, ({{ rows }}, {{ lanes }}), 1)
{% endif %}
    _BLK = ({{ rows }}, {{ lanes }})
{% for line in body_lines %}
    {{ line }}
{% endfor %}
{% if ragged %}
{% for o in out_names %}
    {{ o }} = jnp.where(_rcol < _n, {{ o }}, jnp.zeros_like({{ o }}))
{% endfor %}
{% endif %}
    return ({% for o in out_names %}{{ o }}, {% endfor %})
''',
)

# Flat map+reduce: mask padding lanes with the neutral element against
# the runtime `_n`, then fold the whole array — no cross-step combine
# because there are no grid steps.
_REDUCE_TMPL = KernelTemplate(
    "xla_reduction",
    '''
def {{ name }}_fn(_n_ref, {% for a in in_names %}{{ a }}{{ ", " if not loop.last }}{% endfor %}):
    _n = _n_ref[0, 0]
{% for s in scalar_names %}
    {{ s }} = {{ s }}[0, 0]
{% endfor %}
    _row = jax.lax.broadcasted_iota(jnp.int32, ({{ rows }}, {{ lanes }}), 0)
    _col = jax.lax.broadcasted_iota(jnp.int32, ({{ rows }}, {{ lanes }}), 1)
    i = _row * {{ lanes }} + _col
{% for line in prelude_lines %}
    {{ line }}
{% endfor %}
{% for o in outs %}
    _mapped{{ loop.index0 }} = jnp.asarray({{ o.map_expr }}).astype(jnp.{{ o.dtype }})
    _mapped{{ loop.index0 }} = jnp.where(i < _n, _mapped{{ loop.index0 }}, jnp.asarray({{ o.neutral }}, jnp.{{ o.dtype }}))
    _out{{ loop.index0 }} = {{ o.block_reduce }}(_mapped{{ loop.index0 }}).reshape(1, 1)
{% endfor %}
    return ({% for o in outs %}_out{{ loop.index0 }}, {% endfor %})
''',
)

# Row-segmented map+reduce: mask padding columns, fold axis=1 — the
# whole batch is one "block", so the `_acc<k>` chaining contract (a
# later accumulator referencing an earlier one per row) holds verbatim.
_ROW_REDUCE_TMPL = KernelTemplate(
    "xla_row_reduction",
    '''
def {{ name }}_fn(_n_ref, {% for a in in_names %}{{ a }}{{ ", " if not loop.last }}{% endfor %}):
{% if ragged %}
    _n = _n_ref
{% else %}
    _n = _n_ref[0, 0]
{% endif %}
{% for s in scalar_names %}
    {{ s }} = {{ s }}[0, 0]
{% endfor %}
    _col = jax.lax.broadcasted_iota(jnp.int32, ({{ rows }}, {{ ncols }}), 1)
{% for line in prelude_lines %}
    {{ line }}
{% endfor %}
{% for o in outs %}
    _mapped{{ loop.index0 }} = jnp.asarray({{ o.map_expr }}).astype(jnp.{{ o.dtype }})
    _mapped{{ loop.index0 }} = jnp.where(_col < _n, _mapped{{ loop.index0 }}, jnp.asarray({{ o.neutral }}, jnp.{{ o.dtype }}))
    _acc{{ loop.index0 }} = {{ o.block_reduce }}(_mapped{{ loop.index0 }}, axis=1, keepdims=True)
{% endfor %}
    return ({% for o in outs %}_acc{{ loop.index0 }}, {% endfor %})
''',
)

# Associative scan over the whole stream: the two blocked passes and the
# host carry combine collapse into one cumulative op (+ the neutral
# fold that PallasBackend applies through the carries).
_SCAN_TMPL = KernelTemplate(
    "xla_scan",
    '''
def {{ name }}_fn(x):
    x = x.astype(jnp.{{ dtype }})
    _nv = jnp.asarray({{ neutral }}, jnp.{{ dtype }})
    _s = {{ inclusive_expr }}
{% if exclusive %}
    return jnp.concatenate([_nv.reshape(1), _s[:-1]])
{% else %}
    return _s
{% endif %}
''',
)


def _cumsum_lanes(v):
    return jnp.cumsum(v, axis=-1)


# Names generated XLA source links against beyond the default namespace
# (the snippet function ``cumsumf`` renders as ``cumsum_lanes``).
_KERNEL_LIB = {"cumsum_lanes": _cumsum_lanes}


def _with_preamble(preamble: str, src: str) -> str:
    return (preamble + "\n" + src) if preamble else src


class XlaBackend(Backend):
    name = "xla"
    block_sensitive = False  # code depends on padded shape, never block size

    def fingerprint(self) -> dict:
        return {
            "backend": self.name,
            "target": jax.default_backend(),
            "jax": jax.__version__,
        }

    # -- render (IR -> jitted-jnp source) --------------------------------
    def render_ir(self, kir) -> str:
        """Only axis *extents* matter: the templates compute over the
        whole padded block, so the tiled ``rows.block`` never appears
        in the source (every tuning candidate shares one compile)."""
        if kir.kind == "elementwise":
            src = _ELTWISE_TMPL.render(
                name=kir.name,
                in_names=[a[0] for a in kir.args],
                out_names=[o[0] for o in kir.outs],
                scalar_names=list(kir.meta_get("scalar_names", ())),
                body_lines=kir.lines("body"),
                needs_i=kir.meta_get("needs_i", False),
                ragged=kir.meta_get("ragged", False),
                rows=kir.axis("rows").extent,
                lanes=kir.axes[1].extent,
            )
            return _with_preamble(kir.meta_get("preamble", ""), src)
        if kir.kind == "reduction":
            tmpl_kwargs = dict(
                name=kir.name,
                in_names=[a[0] for a in kir.args],
                scalar_names=list(kir.meta_get("scalar_names", ())),
                prelude_lines=kir.lines("prelude"),
                outs=list(kir.outs),
                rows=kir.axis("rows").extent,
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
            # inclusive-with-neutral: PallasBackend's carries fold the
            # neutral into every element (identity neutrals are no-ops)
            return _SCAN_TMPL.render(
                name=kir.name, dtype=kir.meta_get("dtype"),
                neutral=kir.meta_get("neutral"),
                exclusive=kir.meta_get("exclusive"),
                inclusive_expr=binop_apply(kir.meta_get("binop"),
                                           f"{kir.meta_get('cumop')}(x)",
                                           "_nv"))
        raise ValueError(f"unknown IR kind {kir.kind!r}")

    def _compile(self, src: str, fn_name: str, name: str) -> Callable:
        from repro.core.rtcg import SourceModule

        return jax.jit(SourceModule.load(src, namespace=_KERNEL_LIB,
                                         name=name).get_function(fn_name))

    @staticmethod
    def _arg_meta(kir):
        return [(n, jnp.dtype(d), k) for n, d, k in kir.args]

    # -- elementwise -----------------------------------------------------
    def build_elementwise(self, kir) -> Callable:
        """Same bucket economics as the Pallas driver: the jitted function
        is traced once over the static ``(bucket, LANES)`` shape and the
        runtime ``n`` only pads and slices."""
        bucket = kir.axis("rows").extent
        lanes = kir.axis("lanes").extent
        call = self._compile(self.render_ir(kir), f"{kir.name}_fn", kir.name)
        arg_meta = self._arg_meta(kir)

        def driver(n, flat_args):
            padded = [pad_flat_operand(kind, name, arg, dt, n, bucket, lanes)
                      for (name, dt, kind), arg in zip(arg_meta, flat_args)]
            outs = call(*padded)
            return [o.reshape(-1)[:n] for o in outs]

        return driver

    def build_elementwise_rows(self, kir) -> Callable:
        brows = kir.axis("rows").extent
        ncols = kir.axis("lanes").extent
        call = self._compile(self.render_ir(kir), f"{kir.name}_fn", kir.name)
        arg_meta = self._arg_meta(kir)
        ragged = bool(kir.meta_get("ragged", False))

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

        return driver

    # -- reduction -------------------------------------------------------
    def build_reduction(self, kir) -> Callable:
        bucket = kir.axis("rows").extent
        lanes = kir.axis("lanes").extent
        call = self._compile(self.render_ir(kir), f"{kir.name}_fn", kir.name)
        arg_meta = self._arg_meta(kir)
        multi = kir.meta_get("multi", False)

        def driver(n, flat_args):
            padded = [jnp.full((1, 1), n, dtype=jnp.int32)]
            padded += [pad_flat_operand(kind, name, arg, dt, n, bucket, lanes)
                       for (name, dt, kind), arg in zip(arg_meta, flat_args)]
            outs = call(*padded)
            if multi:
                return tuple(o[0, 0] for o in outs)
            return outs[0][0, 0]

        return driver

    def build_reduction_rows(self, kir) -> Callable:
        brows = kir.axis("rows").extent
        ncols = kir.axis("cols").extent
        call = self._compile(self.render_ir(kir), f"{kir.name}_fn", kir.name)
        arg_meta = self._arg_meta(kir)
        multi = kir.meta_get("multi", False)
        transposed = kir.transposed
        ragged = bool(kir.meta_get("ragged", False))

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

        return driver

    # -- scan ------------------------------------------------------------
    def build_scan(self, kir) -> Callable:
        """Padded to the same ``grid * block_n`` stream as the blocked
        Pallas scan (one traced shape per bucket; neutral padding keeps
        the tail inert), then one associative cumulative op."""
        import numpy as np

        pn = kir.axis("stream.o").extent * kir.axis("stream.i").extent
        dt = jnp.dtype(kir.meta_get("dtype"))
        call = self._compile(self.render_ir(kir), f"{kir.name}_fn", kir.name)
        neutral = kir.meta_get("neutral")

        def driver(n, x):
            xf = jnp.ravel(jnp.asarray(x)).astype(dt)
            if int(xf.size) != pn:
                xf = jnp.pad(xf, (0, pn - int(xf.size)),
                             constant_values=np.asarray(neutral, dt))
            return call(xf)[:n]

        return driver
