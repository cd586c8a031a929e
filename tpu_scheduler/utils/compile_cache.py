"""Compilation: the persistent XLA cache, and compile split from execute.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and this
module sets nothing.  Otherwise the cache lives at one fixed path inside the
checkout: the path is part of the cache's key, so a directory that moves
never hits.  Opt-in from entry points (cli.py, chip_smoke.py)
rather than at import so library users keep control of jax config.

:func:`aot_call` compiles a jitted function ahead of time on a signature
miss, so a compile failure raises :class:`CompileFailed` and only a fault of
the compiled program's execution reaches the backends' ``JaxRuntimeError`` →
``BackendUnavailable`` contract.
"""

from __future__ import annotations

import os
import threading

from ..errors import CompileFailed

__all__ = ["enable_compilation_cache", "DEFAULT_CACHE_DIR", "aot_call"]

DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))), ".jax_cache"
)


def enable_compilation_cache() -> str:
    """Point jax at its persistent compilation cache; returns the directory
    in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    os.makedirs(DEFAULT_CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR


# (fn, static items, arg treedef, leaf avals + shardings) -> jax.stages.Compiled
_EXECUTABLES: dict = {}  # guarded-by: _EXE_LOCK
_EXE_LOCK = threading.Lock()
_EXE_CAP = 256


def aot_call(fn, static: dict, *args, **kwargs):
    """``fn(*args, **static, **kwargs)`` for a ``jax.jit`` function whose
    static arguments are exactly the keys of ``static``.  The executable is
    compiled ahead of time once per signature (the same key jit's own cache
    uses: static values, pytree structure, avals, shardings); a compile
    failure raises :class:`CompileFailed`.  Lowering errors propagate as
    they are."""
    import jax

    leaves, tree = jax.tree.flatten((args, kwargs))
    key = (fn, tuple(sorted(static.items())), tree, tuple((jax.typeof(x), getattr(x, "sharding", None)) for x in leaves))
    with _EXE_LOCK:
        exe = _EXECUTABLES.get(key)
    if exe is None:
        lowered = fn.lower(*args, **static, **kwargs)
        try:
            exe = lowered.compile()
        except jax.errors.JaxRuntimeError as e:
            raise CompileFailed(f"{getattr(fn, '__name__', fn)} failed to compile: {e}") from e
        with _EXE_LOCK:
            _EXECUTABLES[key] = exe
            while len(_EXECUTABLES) > _EXE_CAP:
                _EXECUTABLES.pop(next(iter(_EXECUTABLES)))
    return exe(*args, **kwargs)
