"""The one hook this repo needs that JAX has no public name for.

The repo targets the installed JAX (0.9) and uses its public names
directly (``pltpu.CompilerParams``, ``jax.set_mesh``, ``jax.shard_map``,
``compiled.cost_analysis()``).  What is left here:

  * trace-cache token — jax has no public "fold this value into the jit
    cache key" hook; ``set_trace_token`` rides the ``mesh_context_manager``
    config state: it participates in both the python trace cache
    (``config.trace_context()``) and the C++ jit key
    (``include_in_jit_key=True``), and it is only ever written by
    ``Mesh.__enter__/__exit__``, which this repo never uses (meshes are
    installed with ``jax.set_mesh``), so an appended token survives a whole
    trace/lower block.  If the state ever disappears the shim degrades to a
    no-op and the dispatch layer falls back to its documented trace-cache
    caveat.
"""
from __future__ import annotations


def _trace_token_state():
    try:
        from jax._src import config as jcfg
        cm = getattr(jcfg, "mesh_context_manager", None)
        if cm is not None and hasattr(cm, "set_local") and \
                hasattr(cm, "get_local"):
            return cm
    except Exception:
        pass
    return None


_NO_TOKEN = object()
_TOKEN_TAG = "repro.dispatch"


def set_trace_token(token):
    """Fold ``token`` (hashable, tagged with ``_TOKEN_TAG``) into jax's jit
    trace-cache key for the current thread.

    Used by ``repro.distributed.ctx`` so that re-lowering one jitted
    callable under a different dispatch mesh / rule set re-resolves kernel
    dispatch instead of replaying the stale trace.  The token is appended
    to the carrier state's previous value (a tuple) with any older
    dispatch token stripped first — idempotent, so re-asserting cannot
    stack stale entries.  ``token=None`` means "no dispatch state":
    nothing is appended.  Returns an opaque previous value — pass it back
    to :func:`restore_trace_token` on exit.  Degrades to a no-op (returns
    ``_NO_TOKEN``) if the underlying jax state is gone.
    """
    cm = _trace_token_state()
    if cm is None:
        return _NO_TOKEN
    prev = cm.get_local()
    base = prev if isinstance(prev, tuple) else ()
    base = tuple(e for e in base
                 if not (isinstance(e, tuple) and e and e[0] == _TOKEN_TAG))
    cm.set_local(base if token is None else base + (token,))
    return prev


def restore_trace_token(prev) -> None:
    """Restore the value captured by :func:`set_trace_token`."""
    cm = _trace_token_state()
    if cm is not None and prev is not _NO_TOKEN:
        cm.set_local(prev)
