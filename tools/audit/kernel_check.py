"""Pallas kernel checker (family ``kernel``).

Abstract-evals every registered kernel's grid + BlockSpec structure
without tracing on real data: ``pl.pallas_call`` is monkeypatched with a
recording stub (kernel bodies never run) and each representative case is
driven under ``jax.eval_shape``, so the checks see exactly the grid,
BlockSpecs, out_shapes and scratch shapes the real lowering would.

Per captured call, four proofs (the first three over every grid point):

  * tiling             the last two dims of every block are divisible by
                       (8, 128) or equal the array's — the rule Mosaic,
                       the TPU's Pallas compiler, enforces and interpret
                       mode never checks (a rank-1 block: 128 or whole).
  * index-map bounds   every BlockSpec index map stays inside
                       ``ceil(dim / block)`` for every grid index — a
                       map that walks off the array reads (or writes)
                       padding garbage.
  * disjoint writes    two grid points mapping to the SAME output block
                       may differ only in dims marked "arbitrary"
                       (sequential) in ``dimension_semantics``; differing
                       in a "parallel" dim is a grid-level write race.
  * VMEM footprint     per-step block + scratch bytes stay under a
                       configurable budget (default 16 MiB — one core).

Representative shapes use small blocks (128/256) so every kernel runs a
multi-block grid and the index maps are exercised off the origin.
"""
from __future__ import annotations

import functools
import itertools
import math
from typing import Dict, List, Optional

from tools.audit.framework import (DEFAULT_VMEM_BUDGET, PassResult,
                                   Violation, ensure_importable)


class Record:
    """One captured pallas_call: specs + shapes, no kernel execution."""

    def __init__(self, name, grid, in_specs, out_specs, out_shape,
                 scratch_shapes, compiler_params, operand_shapes):
        self.name = name
        self.grid = grid
        self.in_specs = in_specs
        self.out_specs = out_specs
        self.out_shape = out_shape
        self.scratch_shapes = scratch_shapes
        self.compiler_params = compiler_params
        self.operand_shapes = operand_shapes   # [(shape, dtype), ...]

    @property
    def semantics(self):
        cp = self.compiler_params
        sem = getattr(cp, "dimension_semantics", None) if cp is not None \
            else None
        if sem is None:
            sem = ("arbitrary",) * len(self.grid)   # TPU default: sequential
        return tuple(sem)


def _aslist(x):
    if x is None:
        return []
    return list(x) if isinstance(x, (list, tuple)) else [x]


class PallasCapture:
    """Monkeypatch ``pallas.pallas_call`` with a stub that records the
    call and returns a zeros tree of ``out_shape`` — kernel modules
    resolve ``pl.pallas_call`` at call time, so patching the module
    attribute intercepts every kernel."""

    def __init__(self):
        self.records: List[Record] = []
        self.case: str = "?"

    def __enter__(self):
        from jax.experimental import pallas as pl
        self._pl, self._orig = pl, pl.pallas_call
        cap = self

        def stub(kernel, *, grid=None, in_specs=None, out_specs=None,
                 out_shape=None, scratch_shapes=None, compiler_params=None,
                 interpret=False, **kw):
            def run(*operands):
                import jax.numpy as jnp
                cap.records.append(Record(
                    cap.case, tuple(grid) if grid is not None else (),
                    _aslist(in_specs), _aslist(out_specs),
                    _aslist(out_shape), _aslist(scratch_shapes),
                    compiler_params,
                    [(tuple(o.shape), o.dtype) for o in operands]))
                outs = [jnp.zeros(s.shape, s.dtype)
                        for s in _aslist(out_shape)]
                return outs if isinstance(out_shape, (list, tuple)) \
                    else outs[0]
            return run
        pl.pallas_call = stub
        return self

    def __exit__(self, *exc):
        self._pl.pallas_call = self._orig
        return False


def _grid_points(grid):
    return itertools.product(*(range(int(n)) for n in grid))


def _block_dims(block_shape):
    # a None entry is a squeezed dim of size 1
    return tuple(1 if b is None else int(b) for b in block_shape)


def _check_spec(rec: Record, spec, shape, kind: str, i: int,
                v: List[Violation]) -> Optional[Dict[tuple, list]]:
    """Bounds-check one BlockSpec against its array shape for every grid
    point; returns {block_index_tuple: [grid points]} for disjointness."""
    loc = f"kernel:{rec.name}"
    bs = getattr(spec, "block_shape", None)
    imap = getattr(spec, "index_map", None)
    if bs is None or imap is None:
        return None                       # SMEM / whole-array operand
    blk = _block_dims(bs)
    if len(blk) != len(shape):
        v.append(Violation("kernel-check", loc, 0,
                           f"{kind}[{i}]: block rank {len(blk)} != array "
                           f"rank {len(shape)} (shape {shape})"))
        return None
    for d, align in zip(range(-min(2, len(blk)), 0), (8, 128)[-len(blk):]):
        if blk[d] % align and blk[d] != shape[d]:
            v.append(Violation(
                "kernel-check", loc, 0,
                f"{kind}[{i}]: block {blk} breaks the TPU tiling: dim "
                f"{len(blk) + d} is {blk[d]}, neither a multiple of {align} "
                f"nor the array's {shape[d]} (shape {shape})"))
    nblk = tuple(max(1, math.ceil(d / b)) for d, b in zip(shape, blk))
    blocks: Dict[tuple, list] = {}
    for gp in _grid_points(rec.grid):
        try:
            idx = imap(*gp)
        except Exception as e:
            v.append(Violation("kernel-check", loc, 0,
                               f"{kind}[{i}]: index map raised {e!r} at "
                               f"grid point {gp}"))
            return None
        idx = tuple(int(x) for x in (idx if isinstance(idx, tuple)
                                     else (idx,)))
        if len(idx) != len(blk):
            v.append(Violation("kernel-check", loc, 0,
                               f"{kind}[{i}]: index map returns "
                               f"{len(idx)} indices for rank-{len(blk)} "
                               "blocks"))
            return None
        for d, (x, n) in enumerate(zip(idx, nblk)):
            if not 0 <= x < n:
                v.append(Violation(
                    "kernel-check", loc, 0,
                    f"{kind}[{i}]: index map out of bounds at grid point "
                    f"{gp}: dim {d} block index {x} outside [0, {n}) "
                    f"(shape {shape}, block {blk})"))
                return blocks
        blocks.setdefault(idx, []).append(gp)
    return blocks


def check_record(rec: Record, *, vmem_budget: int = DEFAULT_VMEM_BUDGET
                 ) -> List[Violation]:
    import numpy as np
    v: List[Violation] = []
    loc = f"kernel:{rec.name}"
    if any(int(n) <= 0 for n in rec.grid):
        v.append(Violation("kernel-check", loc, 0,
                           f"degenerate grid {rec.grid}"))
        return v

    # --- input index maps: in-bounds only -----------------------------
    n_ops = len(rec.operand_shapes)
    if rec.in_specs and len(rec.in_specs) != n_ops:
        v.append(Violation("kernel-check", loc, 0,
                           f"{len(rec.in_specs)} in_specs for {n_ops} "
                           "operands"))
    vmem = 0
    for i, (spec, (shape, dtype)) in enumerate(
            zip(rec.in_specs, rec.operand_shapes)):
        blocks = _check_spec(rec, spec, shape, "in", i, v)
        if blocks is not None:
            bs = _block_dims(spec.block_shape)
            vmem += int(np.prod(bs)) * np.dtype(dtype).itemsize

    # --- output index maps: in-bounds + write-disjointness -------------
    sem = rec.semantics
    for i, (spec, sd) in enumerate(zip(rec.out_specs, rec.out_shape)):
        shape = tuple(sd.shape)
        blocks = _check_spec(rec, spec, shape, "out", i, v)
        if blocks is None:
            continue
        bs = _block_dims(spec.block_shape)
        vmem += int(np.prod(bs)) * np.dtype(sd.dtype).itemsize
        for bidx, gps in blocks.items():
            if len(gps) < 2:
                continue
            first = gps[0]
            for gp in gps[1:]:
                racy = [d for d, (a, b) in enumerate(zip(first, gp))
                        if a != b and d < len(sem) and sem[d] == "parallel"]
                if racy:
                    v.append(Violation(
                        "kernel-check", loc, 0,
                        f"out[{i}]: grid points {first} and {gp} both "
                        f"write block {bidx} but differ in parallel grid "
                        f"dim(s) {racy} — write race (mark them "
                        "'arbitrary' or split the block)"))
                    break
            else:
                continue
            break

    # --- per-step VMEM footprint ---------------------------------------
    for s in rec.scratch_shapes:
        shape = getattr(s, "shape", None)
        dtype = getattr(s, "dtype", None)
        if shape is not None and dtype is not None:
            vmem += int(np.prod(shape)) * np.dtype(dtype).itemsize
    if vmem > vmem_budget:
        v.append(Violation(
            "kernel-check", loc, 0,
            f"per-step VMEM footprint {vmem} bytes exceeds budget "
            f"{vmem_budget} (blocks + scratch)"))
    return v


# ---------------------------------------------------------------------------
# representative cases — every KERNEL_OPS entry must appear here (or
# delegate to one that does)
# ---------------------------------------------------------------------------

B, S, C, L, HQ, HKV, D = 2, 512, 256, 1024, 4, 2, 64
POS0 = 256


def _cases():
    import jax.numpy as jnp
    from repro.kernels import (decode_attention as da,
                               flash_attention as fa,
                               flash_attention_bwd as fb,
                               rmsnorm as rn,
                               shared_rmsprop as sr)

    def z(shape, dt=jnp.bfloat16):
        return jnp.zeros(shape, dt)

    def kpos(n):
        return jnp.zeros((B, n), jnp.int32)

    q4, kv4 = z((B, S, HQ, D)), z((B, S, HKV, D))
    lse = z((B, HQ, S), jnp.float32)
    qc = z((B, C, HQ, D))
    qd, cache = z((B, HQ, D)), z((B, L, HKV, D))
    cache8, scale = z((B, L, HKV, D), jnp.int8), z((B, L, HKV, 1),
                                                   jnp.float32)
    k8, s8 = z((B, S, HKV, D), jnp.int8), z((B, S, HKV, 1), jnp.float32)
    pos = jnp.zeros((B,), jnp.int32)
    x2, sc2 = z((512, 512)), z((512,))
    g2 = z((512, 1024), jnp.float32)

    return {
        "flash_attention": [
            ("flash_fwd", lambda: fa.flash_attention_fwd(
                q4, kv4, kv4, causal=True, block_q=128, block_k=128,
                save_residuals=True, interpret=True)),
            ("flash_fwd_window", lambda: fa.flash_attention_fwd(
                q4, kv4, kv4, causal=True, window=256, block_q=256,
                block_k=128, interpret=True)),
            ("flash_bwd", lambda: fb.flash_attention_bwd(
                q4, kv4, kv4, q4, lse, q4, causal=True, block_q=128,
                block_k=128, interpret=True)),
        ],
        "flash_append": [
            ("append", lambda: fa.flash_attention_append(
                qc, kv4, kv4, kpos(S), pos0=POS0, block_q=128,
                block_k=128, interpret=True)),
            ("append_quant", lambda: fa.flash_attention_append(
                qc, k8, k8, kpos(S), pos0=POS0, block_q=128, block_k=128,
                k_scale=s8, v_scale=s8, interpret=True)),
        ],
        # speculative verify delegates to flash_append after re-basing
        # per-row depths to a static pos0 = cache_len: one q block of
        # drafted tokens against a deep prefix keystream.  The q-offset
        # index maps run far off the origin here (pos0 >> chunk), the
        # regime a bad offset map walks out of bounds in.
        "flash_verify": [
            ("verify_append", lambda: fa.flash_attention_append(
                z((B, 128, HQ, D)), z((B, L + 128, HKV, D)),
                z((B, L + 128, HKV, D)), kpos(L + 128), pos0=L,
                block_q=128, block_k=128, interpret=True)),
        ],
        "decode_attention": [
            ("decode_fwd", lambda: da.decode_attention_fwd(
                qd, cache, cache, kpos(L), pos, block_k=256,
                interpret=True)),
            ("decode_partials", lambda: da.decode_attention_partials(
                qd, cache, cache, kpos(L), pos, block_k=256,
                interpret=True)),
            ("decode_quant", lambda: da.decode_attention_fwd(
                qd, cache8, cache8, kpos(L), pos, block_k=256,
                k_scale=scale, v_scale=scale, interpret=True)),
        ],
        "rmsnorm": [
            ("rmsnorm_fwd", lambda: rn.rmsnorm_fwd(
                x2, sc2, block_rows=128, save_residuals=True,
                interpret=True)),
            ("rmsnorm_bwd", lambda: rn.rmsnorm_bwd(
                x2, sc2, z((512,), jnp.float32), x2, block_rows=128,
                interpret=True)),
        ],
        "rmsprop_update": [
            ("rmsprop_2d", lambda: sr.rmsprop_update_2d(
                g2, g2, jnp.float32(1e-3), block_rows=128,
                interpret=True)),
        ],
    }


def run_kernel_checks(root: str, *,
                      vmem_budget: int = DEFAULT_VMEM_BUDGET
                      ) -> List[PassResult]:
    ensure_importable(root)
    import jax
    from repro.kernels import dispatch

    cases = _cases()
    v: List[Violation] = []
    records: List[Record] = []
    with PallasCapture() as cap:
        for op, case_list in cases.items():
            for name, fn in case_list:
                cap.case = name
                before = len(cap.records)
                try:
                    jax.eval_shape(fn)
                except Exception as e:
                    v.append(Violation("kernel-check", f"kernel:{name}", 0,
                                       f"abstract eval failed: {e!r}"))
                    continue
                if len(cap.records) == before:
                    v.append(Violation(
                        "kernel-check", f"kernel:{name}", 0,
                        "case captured no pallas_call — kernel path not "
                        "exercised"))
        records = cap.records

    grid_points = 0
    for rec in records:
        grid_points += int(math.prod(int(n) for n in rec.grid)) \
            if rec.grid else 0
        v.extend(check_record(rec, vmem_budget=vmem_budget))

    # coverage: every registered op has cases, directly or via delegate
    covered = set(cases)
    for op, c in dispatch.KERNEL_OPS.items():
        if op in covered:
            continue
        if c.delegate is not None and c.delegate in covered:
            continue
        v.append(Violation("kernel-check", "tools/audit/kernel_check.py",
                           0, f"registered op '{op}' has no "
                           "representative case (and no covered "
                           "delegate)"))
    stats = {"cases": sum(len(c) for c in cases.values()),
             "pallas_calls": len(records),
             "grid_points_checked": grid_points,
             "vmem_budget": vmem_budget}
    return [PassResult("kernel-check", "kernel", v, stats)]
