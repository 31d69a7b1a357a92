"""Sharding context: lets mesh-agnostic model code request activation
sharding constraints that only take effect when the launcher has installed a
rule set (no-ops on single-device CPU runs, so tests/benches are unaffected).

Also carries the *dispatch mesh*: the mesh the launcher is lowering for.
The kernel dispatch layer (``repro.kernels.dispatch``) keys backend
selection off this mesh's device platform — the lowering *target* — rather
than ``jax.default_backend()``, so a host process lowering for a TPU mesh
picks the same kernels the TPU mesh will run.

Dispatch resolves at *trace* time, but jax caches traces by function
identity — without countermeasures, re-lowering one jitted callable under
a different mesh would replay the stale dispatch decision baked into the
cached trace.  ``use_mesh`` and ``sharding_rules`` therefore install a
*dispatch token* (a hashable digest of the mesh + rule set) into jax's jit
cache key via ``compat.set_trace_token``; switching meshes changes the key
and the callable re-traces, re-running dispatch resolution.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional

import jax

from repro import compat

_state = threading.local()


# ---------------------------------------------------------------------------
# dispatch trace token
# ---------------------------------------------------------------------------

def _freeze(v):
    """Hashable digest of a rules/mesh value (dicts recursed, arrays et al
    collapsed to repr — the token only needs equality, not round-tripping)."""
    try:
        hash(v)
        return v
    except TypeError:
        pass
    if isinstance(v, dict):
        return tuple(sorted((k, _freeze(x)) for k, x in v.items()))
    if isinstance(v, (list, tuple)):
        return tuple(_freeze(x) for x in v)
    return repr(v)


def dispatch_token():
    """The current dispatch-relevant state as a jit-cache-key component
    (None when no mesh or rules are installed — nothing to assert)."""
    mesh = getattr(_state, "mesh", None)
    rules = getattr(_state, "rules", None)
    if mesh is None and rules is None:
        return None
    return (compat._TOKEN_TAG, _freeze(mesh), _freeze(rules))


def _install_token():
    return compat.set_trace_token(dispatch_token())


def current_rules() -> Optional[Dict[str, jax.sharding.PartitionSpec]]:
    return getattr(_state, "rules", None)


@contextlib.contextmanager
def sharding_rules(rules: Dict[str, jax.sharding.PartitionSpec]):
    """rules: logical-name -> PartitionSpec (e.g. "residual", "expert_buffer").
    Installed by the launcher around trace/lower time.  Folds the rule set
    into the jit cache key (see module docstring) so cached traces are not
    replayed across rule-set changes."""
    prev = current_rules()
    _state.rules = rules
    tok = _install_token()
    try:
        yield
    finally:
        _state.rules = prev
        compat.restore_trace_token(tok)


def constrain(x, name: str):
    """Apply the named activation constraint if a rule set is installed."""
    rules = current_rules()
    if rules is None or name not in rules:
        return x
    return jax.lax.with_sharding_constraint(x, rules[name])


# ---------------------------------------------------------------------------
# dispatch mesh
# ---------------------------------------------------------------------------

def current_mesh():
    """The mesh installed by the launcher (None on plain single-device runs)."""
    return getattr(_state, "mesh", None)


@contextlib.contextmanager
def use_mesh(mesh):
    """Install ``mesh`` as the kernel-dispatch target around trace/lower time.

    Orthogonal to ``jax.set_mesh`` (which feeds jax's sharding machinery):
    this one makes the mesh *visible* to the dispatch layer so it can
    shard_map the Pallas kernels over it and resolve the target platform,
    and folds the mesh into the jit cache key (see module docstring) so one
    jitted callable re-lowered under a different mesh re-resolves dispatch
    instead of replaying the stale trace."""
    prev = current_mesh()
    _state.mesh = mesh
    tok = _install_token()
    try:
        yield mesh
    finally:
        _state.mesh = prev
        compat.restore_trace_token(tok)


def mesh_platform(mesh) -> str:
    """Device platform of ``mesh`` ("cpu"/"tpu"/"gpu").  AbstractMesh carries
    no devices; assume the local default backend in that case."""
    devs = getattr(mesh, "devices", None)
    if devs is None:
        return jax.default_backend()
    return devs.flat[0].platform


def current_platform() -> str:
    """Platform of the lowering target: the dispatch mesh's device platform
    when a mesh is installed, else the process default backend."""
    mesh = current_mesh()
    if mesh is None:
        return jax.default_backend()
    return mesh_platform(mesh)


def mesh_devices(mesh) -> int:
    """Total device count of a (possibly abstract) mesh."""
    n = 1
    for s in dict(mesh.shape).values():
        n *= s
    return n
