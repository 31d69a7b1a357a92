"""The repro-audit suite is itself a tier-1 surface: the clean tree must
pass ``--strict``, and every pass family must flag its known-bad fixture
(a checker that cannot fail is not checking anything)."""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(ROOT, "tools", "audit", "fixtures")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)       # makes `tools.audit` importable

from tools.audit import run_audit                      # noqa: E402
from tools.audit import alloc_model, ast_passes, contracts, \
    kernel_check                                       # noqa: E402
from tools.audit.framework import summary_line         # noqa: E402


def _load_fixture(name):
    path = os.path.join(FIXTURES, name)
    spec = importlib.util.spec_from_file_location(
        "audit_fixture_" + os.path.basename(name)[:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# clean tree
# ---------------------------------------------------------------------------

def test_strict_audit_clean_on_tree(tmp_path):
    """The committed tree passes every audit pass; AUDIT.json carries the
    allocator coverage counters the acceptance contract pins."""
    report = run_audit(ROOT, strict=True)
    bad = [v for p in report["passes"] for v in p["violations"]]
    assert not bad, "\n".join(f"{v['path']}:{v['line']}: {v['message']}"
                              for v in bad)
    assert report["summary"]["passes_failed"] == 0
    # all four families ran
    assert {p["family"] for p in report["passes"]} == \
        {"ast", "contract", "kernel", "allocator"}
    # the interleaving check actually explored state space and reached
    # both the COW-fork and recycled-page-reuse paths
    am = report["allocator_model"]
    assert am["states_explored"] >= alloc_model.STATE_FLOOR
    assert am["cow_forks"] > 0
    assert am["recycle_reuse"] > 0
    assert am["reserved_allocs"] > 0 and am["preempts"] > 0
    assert am["spec_allocs"] > 0 and am["rewinds"] > 0 \
        and am["spec_commits"] > 0
    # the kernel checker exercised multi-block grids
    kstats = next(p["stats"] for p in report["passes"]
                  if p["name"] == "kernel-check")
    assert kstats["pallas_calls"] >= 10
    assert kstats["grid_points_checked"] > 100
    line = summary_line(report)
    assert line.startswith("audit,ok,") and "violations=0" in line
    # report round-trips through json
    json.loads(json.dumps(report))


def test_cli_runner_strict_exit_code(tmp_path):
    """``python -m tools.audit --strict`` (the CI entry) exits 0 on the
    clean tree and writes the AUDIT.json artifact where asked."""
    out = tmp_path / "AUDIT.json"
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    proc = subprocess.run(
        [sys.executable, "-m", "tools.audit", "--strict", "--only", "ast",
         "--only", "contract", "--only", "allocator", "--json", str(out)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    report = json.loads(out.read_text())
    assert report["summary"]["violations"] == 0
    assert report["allocator_model"]["cow_forks"] > 0


def test_subset_run_never_clobbers_root_artifact(tmp_path):
    """A ``--only`` subset run without ``--json`` must not overwrite the
    committed <repo>/AUDIT.json — a 1-pass report in the full-suite slot
    misrepresents coverage (the artifact CI uploads and consumers diff)."""
    root_artifact = os.path.join(ROOT, "AUDIT.json")
    before = open(root_artifact, "rb").read()
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    proc = subprocess.run(
        [sys.executable, "-m", "tools.audit", "--strict",
         "--only", "no-ops-import"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "report:" not in proc.stdout       # no report file claimed
    assert open(root_artifact, "rb").read() == before
    # an explicit --json still writes the subset report where asked
    out = tmp_path / "subset.json"
    proc = subprocess.run(
        [sys.executable, "-m", "tools.audit", "--strict",
         "--only", "no-ops-import", "--json", str(out)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert json.loads(out.read_text())["summary"]["passes_total"] == 1


# ---------------------------------------------------------------------------
# AST passes vs fixtures
# ---------------------------------------------------------------------------

AST_CASES = [
    ("no-ops-import", "bad_ast/ops_import.py", 4),
    ("kernel-import-containment", "bad_ast/kernel_import.py", 3),
    ("no-step-key-rebuild", "bad_ast/step_key.py", 2),
    ("no-default-backend", "bad_ast/repro/kernels/default_backend.py", 1),
    ("fallback-reason", "bad_ast/repro/kernels/bare_fallback.py", 2),
]


@pytest.mark.parametrize("pass_name,fixture,n_min",
                         [pytest.param(*c, id=c[0]) for c in AST_CASES])
def test_ast_pass_flags_fixture(pass_name, fixture, n_min):
    p = next(p for p in ast_passes.PASSES if p.name == pass_name)
    res = ast_passes.run_pass(p, ROOT,
                              files=[os.path.join(FIXTURES, fixture)])
    assert len(res.violations) >= n_min, \
        f"{pass_name} missed its fixture: {[v.format() for v in res.violations]}"
    assert all(v.pass_name == pass_name for v in res.violations)


def test_step_key_pass_spares_setup_code():
    """Keys built OUTSIDE step functions are legitimate — the fixture's
    ``warmup`` must not be flagged."""
    p = next(p for p in ast_passes.PASSES
             if p.name == "no-step-key-rebuild")
    res = ast_passes.run_pass(
        p, ROOT, files=[os.path.join(FIXTURES, "bad_ast/step_key.py")])
    assert not any("warmup" in v.message for v in res.violations)


def test_ops_import_allow_escape(tmp_path):
    """The ``lint: allow-ops-ref`` escape suppresses a flagged line —
    tests asserting the import FAILS rely on it."""
    f = tmp_path / "escape.py"
    f.write_text("import importlib\n"
                 "importlib.import_module('repro.kernels' + '.ops')"
                 "  # lint: allow-ops-ref\n")
    p = next(p for p in ast_passes.PASSES if p.name == "no-ops-import")
    res = ast_passes.run_pass(p, ROOT, files=[str(f)])
    assert res.ok, [v.format() for v in res.violations]


# ---------------------------------------------------------------------------
# contract passes
# ---------------------------------------------------------------------------

def test_decision_rows_flags_silent_resolver():
    res = contracts.check_decision_rows(
        ROOT, dispatch_src=os.path.join(FIXTURES, "bad_dispatch.py"))
    silent = [v for v in res.violations if "without a _decide" in v.message]
    assert silent, [v.format() for v in res.violations]
    assert all(v.pass_name == "resolver-decision-rows"
               for v in res.violations)


def test_registry_covers_every_backend_entry():
    """Every public dispatch entry taking backend= is registered in
    KERNEL_OPS — the reverse-direction contract that keeps new arms from
    escaping the audit."""
    res = contracts.check_registry_oracles(ROOT)
    assert res.ok, [v.format() for v in res.violations]
    assert res.stats["ops"] >= 7


def test_cache_leaf_sharding_contract():
    """Every cache leaf (f32/int8 x contiguous/paged, scale leaves
    included) hits an explicit cache_shardings rule, rank-matched to its
    payload."""
    res = contracts.check_cache_leaf_sharding(ROOT)
    assert res.ok, [v.format() for v in res.violations]
    assert res.stats["leaves_checked"] >= 16


# ---------------------------------------------------------------------------
# kernel checker vs fixture
# ---------------------------------------------------------------------------

def test_kernel_checker_flags_bad_kernel():
    import jax
    bad = _load_fixture("bad_kernel.py")
    with kernel_check.PallasCapture() as cap:
        cap.case = "bad_kernel"
        jax.eval_shape(bad.run)
    assert len(cap.records) == 1
    v = kernel_check.check_record(cap.records[0])
    msgs = " | ".join(x.message for x in v)
    assert "out of bounds" in msgs, msgs
    assert "write race" in msgs, msgs
    assert "exceeds budget" in msgs, msgs
    assert "breaks the TPU tiling" in msgs, msgs


def test_kernel_checker_budget_is_configurable():
    """A tighter budget flags even the healthy decode kernel — proves the
    VMEM accounting is live, not vacuously passing."""
    results = kernel_check.run_kernel_checks(ROOT, vmem_budget=1024)
    assert any("exceeds budget" in v.message
               for r in results for v in r.violations)


# ---------------------------------------------------------------------------
# allocator interleaving vs fixture
# ---------------------------------------------------------------------------

def test_alloc_model_flags_missing_version_bump():
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.serve import AllocatorModel
    bad = _load_fixture("bad_alloc.py")
    violations, stats = alloc_model.explore(
        AllocatorModel(n_pages=4,
                       allocator_cls=bad.NoVersionBumpAllocator))
    assert any("version" in v.message for v in violations), \
        [v.format() for v in violations]
    assert stats["states_explored"] > 1


def test_alloc_replay_flags_refcount_underflow():
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.serve import PageAllocator
    bad = _load_fixture("bad_alloc.py")
    v = alloc_model.replay_trace(PageAllocator(4), bad.UNDERFLOW_TRACE)
    assert any("negative" in x.message for x in v), \
        [x.format() for x in v]


def test_alloc_replay_flags_rollback_leak():
    """A verify round that pre-allocates two speculative pages but only
    rewinds one leaks the other's refcount — the replay harness must
    report the unresolved hold when the trace ends."""
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.serve import PageAllocator
    bad = _load_fixture("bad_alloc.py")
    v = alloc_model.replay_trace(PageAllocator(4),
                                 bad.LEAKY_ROLLBACK_TRACE)
    assert any("never rewound or committed" in x.message for x in v), \
        [x.format() for x in v]
    # the balanced round is clean: both pages resolved
    ok = alloc_model.replay_trace(
        PageAllocator(4), (("spec_alloc",), ("spec_alloc",),
                           ("rewind", 2), ("commit", 1)))
    assert not ok, [x.format() for x in ok]


def test_alloc_model_flags_phantom_reservation():
    """An allocator whose ``reserve`` never checks capacity breaks the
    "reserved allocs cannot fail" contract — the explorer must reach an
    overbooked state and flag it (and nothing else: this fixture's
    version/refcount discipline is correct)."""
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.serve import AllocatorModel
    bad = _load_fixture("bad_alloc.py")
    violations, stats = alloc_model.explore(
        AllocatorModel(n_pages=4,
                       allocator_cls=bad.PhantomReserveAllocator))
    assert any("reserved" in v.message and "exceeds free" in v.message
               for v in violations), [v.format() for v in violations]
    assert not any("version" in v.message for v in violations)


def test_alloc_model_real_allocator_is_clean():
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.serve import AllocatorModel
    violations, stats = alloc_model.explore(AllocatorModel(n_pages=4))
    assert not violations, [v.format() for v in violations]
    assert stats["cow_forks"] > 0 and stats["recycle_reuse"] > 0
    # the robustness ops are part of the modeled vocabulary, and the
    # state count clears the anti-shrink floor the strict run enforces
    assert stats["reserve_ops"] > 0
    assert stats["reserved_allocs"] > 0
    assert stats["preempts"] > 0
    # the speculative family (verify pre-alloc, rejected-draft rewind,
    # accepted-draft commit) is modeled and reached
    assert stats["spec_allocs"] > 0
    assert stats["rewinds"] > 0
    assert stats["spec_commits"] > 0
    assert stats["states_explored"] >= alloc_model.STATE_FLOOR


# ---------------------------------------------------------------------------
# regression pins for violations fixed in this change
# ---------------------------------------------------------------------------

def test_default_interpret_follows_lowering_target(monkeypatch):
    """Kernel modules used to key interpret-mode off the HOST backend
    (``jax.default_backend() == "cpu"``); they now follow the lowering
    target, so a CPU host lowering for a TPU mesh compiles Mosaic instead
    of silently interpreting."""
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.kernels import _interpret
    assert _interpret.default_interpret() is True      # CPU dev box
    monkeypatch.setattr(_interpret.ctx, "current_platform", lambda: "tpu")
    assert _interpret.default_interpret() is False
    monkeypatch.setattr(_interpret.ctx, "current_platform",
                        lambda: "gpu")
    assert _interpret.default_interpret() is True      # TPU-only kernels


def test_no_kernel_module_reads_default_backend():
    """The concrete violations this audit surfaced (5 sites keying
    interpret off the host platform) stay fixed."""
    p = next(p for p in ast_passes.PASSES
             if p.name == "no-default-backend")
    res = ast_passes.run_pass(p, ROOT)
    assert res.ok, [v.format() for v in res.violations]
