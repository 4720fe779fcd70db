"""Translate the paper's C-like operation snippets into jnp expressions.

PyCUDA's ElementwiseKernel/ReductionKernel users write tiny C snippets
("z[i] = a*x[i] + b*y[i]").  To keep the user-facing surface of the
reproduction faithful, we accept the same snippets and translate them to
the jnp dialect used inside generated Pallas kernels:

  * ``name[i]``      -> the block-local array ``name``
  * C math calls     -> jnp equivalents (expf -> jnp.exp, ...)
  * ``cond ? a : b`` -> jnp.where(cond, a, b)
  * ``float t = e;`` -> ``t = e``
  * ``&&  ||  !``    -> ``&  |  ~`` (with parenthesization caveats noted)

This is deliberately a *simple textual* translation — the paper's first
strategy ("simple textual keyword replacement ... suffices for a
surprisingly large range of use cases"), not a C parser.
"""

from __future__ import annotations

import re

C_FUNC_MAP = {
    "sqrtf": "jnp.sqrt", "sqrt": "jnp.sqrt",
    "expf": "jnp.exp", "exp": "jnp.exp",
    "logf": "jnp.log", "log": "jnp.log",
    "fabsf": "jnp.abs", "fabs": "jnp.abs", "abs": "jnp.abs",
    "powf": "jnp.power", "pow": "jnp.power",
    "fminf": "jnp.minimum", "fmin": "jnp.minimum", "min": "jnp.minimum",
    "fmaxf": "jnp.maximum", "fmax": "jnp.maximum", "max": "jnp.maximum",
    "sinf": "jnp.sin", "sin": "jnp.sin",
    "cosf": "jnp.cos", "cos": "jnp.cos",
    "tanhf": "jnp.tanh", "tanh": "jnp.tanh",
    "rsqrtf": "jax.lax.rsqrt", "rsqrt": "jax.lax.rsqrt",
    "floorf": "jnp.floor", "ceilf": "jnp.ceil",
    "erff": "jax.lax.erf", "sigmoid": "jax.nn.sigmoid",
    # row-wise inclusive prefix sum (last-axis): the sampler's
    # inverse-CDF epilogue fuses into the ragged flush through this.
    # The XLA backend links `cumsum_lanes` to jnp.cumsum; Pallas TPU has
    # no cumsum lowering, so its driver scans such an output after the
    # kernel (`backends.pallas.split_row_scans`).
    "cumsumf": "cumsum_lanes",
}

_DECL_RE = re.compile(r"^\s*(?:const\s+)?(?:float|double|int|long|unsigned\s+int|bool)\s+(\w+)\s*=")
_SUBSCRIPT_RE = re.compile(r"\b([A-Za-z_]\w*)\s*\[\s*i\s*\]")
_FUNC_RE = re.compile(r"\b(" + "|".join(sorted(C_FUNC_MAP, key=len, reverse=True)) + r")\s*\(")


def _rewrite_ternary_once(e: str) -> str | None:
    """Rewrite one (possibly parenthesized/nested) C ternary to jnp.where."""
    q = e.find("?")
    if q < 0:
        return None
    # condition: scan left until an unmatched '(' or a top-level ','
    depth = 0
    start = 0
    for j in range(q - 1, -1, -1):
        c = e[j]
        if c == ")":
            depth += 1
        elif c == "(":
            if depth == 0:
                start = j + 1
                break
            depth -= 1
        elif c == "," and depth == 0:
            start = j + 1
            break
    # then/else: scan right for the ':' at depth 0, stop at unmatched ')'
    depth = 0
    colon = None
    end = len(e)
    for j in range(q + 1, len(e)):
        c = e[j]
        if c == "(":
            depth += 1
        elif c == ")":
            if depth == 0:
                end = j
                break
            depth -= 1
        elif c == ":" and depth == 0 and colon is None:
            colon = j
        elif c == "," and depth == 0 and colon is not None:
            end = j
            break
    if colon is None:
        return None
    cond, a, b = e[start:q].strip(), e[q + 1:colon].strip(), e[colon + 1:end].strip()
    return e[:start] + f"jnp.where({cond}, {a}, {b})" + e[end:]


def translate_expression(expr: str) -> str:
    """Translate one C-like expression to a jnp expression string."""
    e = expr.strip()
    while "?" in e:
        rewritten = _rewrite_ternary_once(e)
        if rewritten is None:
            break
        e = rewritten
    e = _SUBSCRIPT_RE.sub(lambda m: m.group(1), e)
    e = _FUNC_RE.sub(lambda m: C_FUNC_MAP[m.group(1)] + "(", e)
    e = e.replace("&&", "&").replace("||", "|")
    e = re.sub(r"!(?![=])", "~", e)
    # float literal suffixes: 1.0f -> 1.0
    e = re.sub(r"(\d+\.?\d*(?:[eE][+-]?\d+)?)[fF]\b", r"\1", e)
    return e


def split_statements(operation: str) -> list[str]:
    return [s.strip() for s in operation.split(";") if s.strip()]


_AUG_RE = re.compile(r"^\s*([A-Za-z_]\w*\s*\[\s*i\s*\]|[A-Za-z_]\w*)\s*([+\-*/])=\s*(.+)$")
_CMP_PROTECT = [("==", "\0EQ\0"), ("!=", "\0NE\0"), ("<=", "\0LE\0"), (">=", "\0GE\0")]


def _protect(s: str) -> str:
    for op, tok in _CMP_PROTECT:
        s = s.replace(op, tok)
    return s


def _unprotect(s: str) -> str:
    for op, tok in _CMP_PROTECT:
        s = s.replace(tok, op)
    return s


def translate_statement(stmt: str) -> tuple[str | None, str]:
    """-> (assignment target or None, translated expression/statement).

    Targets of the form ``name[i]`` are flagged as *vector writes* by
    returning the bare name; plain names are temporaries.
    """
    stmt = stmt.strip()
    m = _DECL_RE.match(stmt)
    if m:
        # drop the C type: slice at the *match position* of the declared
        # name, never a substring search (a name like 't' also occurs
        # inside 'float', and index() would cut there)
        stmt = stmt[m.start(1):]
    m = _AUG_RE.match(stmt)
    if m:  # z[i] *= 2  ->  z[i] = z[i] * (2)
        lhs, op, rhs = m.groups()
        stmt = f"{lhs} = {lhs} {op} ({rhs})"
    protected = _protect(stmt)
    if "=" in protected:
        lhs, rhs = protected.split("=", 1)
        lhs, rhs = _unprotect(lhs).strip(), _unprotect(rhs)
        sub = _SUBSCRIPT_RE.fullmatch(lhs)
        target = sub.group(1) if sub else lhs
        return target, translate_expression(rhs)
    return None, translate_expression(stmt)


def translate_assignment(stmt: str) -> str:
    """Translate one C-dialect *assignment* (``_t0 = expf(v0[i])``) to a
    jnp statement line.  Used for hoisted common-subexpression preludes
    in generated kernels: the fusion planner names repeated subtrees
    ``_t<k>`` and the kernel computes each once per block, before the
    map/output expressions that reference it."""
    tgt, expr = translate_statement(stmt)
    if tgt is None:
        raise ValueError(f"prelude statement is not an assignment: {stmt!r}")
    return f"{tgt} = {expr}"


def written_names(operation: str) -> list[str]:
    """Vector names assigned via ``name[i] = ...`` in declaration order."""
    seen: list[str] = []
    for stmt in split_statements(operation):
        tgt, _ = translate_statement(stmt)
        if tgt and tgt not in seen and re.search(rf"\b{re.escape(tgt)}\s*\[\s*i\s*\]\s*[+\-*/]?=(?!=)", stmt):
            seen.append(tgt)
    return seen


def parse_c_arguments(arguments: str) -> list[tuple[str, str, bool]]:
    """Parse 'float a, float *x' -> [(name, dtype, is_vector), ...]."""
    ctype_map = {
        "float": "float32", "double": "float64", "int": "int32",
        "long": "int64", "unsigned": "uint32", "bool": "bool_",
        "half": "bfloat16", "bfloat16": "bfloat16",
    }
    out: list[tuple[str, str, bool]] = []
    for part in arguments.split(","):
        part = part.strip()
        if not part:
            continue
        is_vec = "*" in part
        part = part.replace("*", " ")
        toks = [t for t in part.split() if t not in ("const", "__restrict__")]
        ctype, name = toks[0], toks[-1]
        out.append((name, ctype_map.get(ctype, ctype), is_vec))
    return out
