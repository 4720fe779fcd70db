"""Run-time code generation core — the `SourceModule` analogue (paper §5).

PyCUDA turns a CUDA-C string into loaded GPU binaries at run time.  The
TPU/JAX equivalent of "low-level source" is *Pallas/JAX Python source*:
a string of Python defining kernels, exec'd into a sandboxed namespace
and wrapped by `pl.pallas_call` / `jax.jit`.  The XLA/Mosaic compiler
plays the role nvcc played; JAX's persistent compilation cache plus our
`DiskCache` play the role of PyCUDA's compiler cache.

The user never touches the compiler; source goes in, a callable comes
out, and repeated loads of identical source are free (Fig. 2 workflow).
"""

from __future__ import annotations

import functools
import linecache
import os
import threading
from typing import Any, Callable

from repro.core.cache import LRUCache, stable_hash

# Bounded: identity-keyed namespace tokens mean loads with fresh (even
# equal) value objects mint new entries, so an unbounded dict would leak
# one exec'd module per call in pathological loops.  Eviction is safe —
# worst case a re-exec; an evicted entry's key can never produce a stale
# hit because the entry is gone with its values.
_module_registry: LRUCache = LRUCache(
    maxsize=int(os.environ.get("REPRO_MODULE_REGISTRY_SIZE", "512")))


def _default_namespace() -> dict[str, Any]:
    """Names available to generated source — the 'runtime library' the
    generated kernels link against."""
    import functools as _functools
    import math as _math

    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    ns: dict[str, Any] = {
        "jax": jax,
        "jnp": jnp,
        "lax": lax,
        "pl": pl,
        "functools": _functools,
        "math": _math,
        "partial": _functools.partial,
    }
    from jax.experimental.pallas import tpu as pltpu

    ns["pltpu"] = pltpu
    return ns


class SourceModule:
    """Compile generated Python/Pallas source into callables.

    Mirrors ``pycuda.compiler.SourceModule``:

    >>> mod = SourceModule('''
    ... def multiply_by_two(x):
    ...     return x * 2
    ... ''')
    >>> f = mod.get_function("multiply_by_two")

    The module-level exec happens once per distinct source text
    (content-addressed registry); `get_function` returns the raw python
    callable, `get_jit_function` a jitted one.
    """

    def __init__(self, source: str, namespace: dict | None = None, name: str | None = None):
        self.source = source
        self.key = stable_hash(source)
        self.name = name or f"rtcg_{self.key[:12]}"
        self._ns = _default_namespace()
        if namespace:
            self._ns.update(namespace)
        # Register the source with linecache so tracebacks/introspection
        # show generated code (error reporting is a paper requirement).
        fname = f"<rtcg:{self.name}>"
        linecache.cache[fname] = (len(source), None, source.splitlines(True), fname)
        code = compile(source, fname, "exec")
        exec(code, self._ns)

    @classmethod
    def load(cls, source: str, namespace: dict | None = None, name: str | None = None) -> "SourceModule":
        """Content-addressed load: identical source + namespace -> same module.

        The namespace token hashes keys AND value *identities* (``id``),
        so two loads binding the same names to different objects never
        collide — ``repr`` would be lossy here (e.g. large numpy arrays
        truncate to identical strings).  Identity is stable because the
        registered module's namespace keeps every value alive, so a live
        entry's ids can never be reused.  Equal-but-distinct values get
        duplicate modules — conservative in the safe direction (never a
        wrong module).
        """
        key = stable_hash(source) + ("" if namespace is None else
                                     stable_hash(sorted((k, f"{type(v).__name__}@{id(v)}")
                                                        for k, v in namespace.items())))
        return _module_registry.get_or_create(
            key, lambda: cls(source, namespace=namespace, name=name))

    def get_function(self, name: str) -> Callable:
        try:
            fn = self._ns[name]
        except KeyError:
            raise NameError(
                f"generated module {self.name!r} defines no function {name!r}; "
                f"available: {[k for k, v in self._ns.items() if callable(v) and not k.startswith('_')][:20]}"
            ) from None
        if not callable(fn):
            raise TypeError(f"{name!r} in generated module is not callable")
        return fn

    def get_jit_function(self, name: str, **jit_kwargs) -> Callable:
        return functools.partial(_jit_cached, self.key, name, self.get_function(name), _freeze(jit_kwargs))


_jit_table: dict[tuple, Callable] = {}
_jit_lock = threading.Lock()


def _freeze(d: dict):
    return tuple(sorted(d.items()))


def _jit_cached(key, name, fn, frozen_kwargs, *args, **kwargs):
    import jax

    tkey = (key, name, frozen_kwargs)
    with _jit_lock:
        jfn = _jit_table.get(tkey)
        if jfn is None:
            jfn = jax.jit(fn, **dict(frozen_kwargs))
            _jit_table[tkey] = jfn
    return jfn(*args, **kwargs)


def registry_size() -> int:
    return len(_module_registry)


def clear_registry() -> None:
    _module_registry.clear()
    with _jit_lock:
        _jit_table.clear()
