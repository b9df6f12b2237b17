"""Tier-1 collection-time guard: metrics-registry names must stay literal,
unique, canonical (``subsystem.noun_unit``; counters ``_total``,
histograms ``_seconds``) and documented in docs/observability.md
(``scripts/check_metric_names.py``).

Runs at IMPORT (= pytest collection) so a refactor that duplicates a
metric name, computes one dynamically, or adds one without documenting it
fails the suite even though nothing behavioral notices telemetry rotting."""
import importlib.util
import os

_script = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "scripts", "check_metric_names.py")
_spec = importlib.util.spec_from_file_location("check_metric_names", _script)
_lint = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_lint)

_problems = _lint.check()
if _problems:  # collection-time failure, with the drifted names
    raise AssertionError(
        "metric-name hygiene drifted: " + "; ".join(_problems))


def test_metric_names_clean():
    assert _lint.check() == []


def test_scanner_sees_known_instrumentation():
    """The AST scanner must actually find the load-bearing metrics — a
    scanner that silently matches nothing would always pass."""
    regs, bad = _lint.registrations()
    assert bad == []
    for expected in ("train.step_seconds", "serving.shed_total",
                     "worker.task_seconds", "fault.fired_total"):
        assert expected in regs, expected


def test_convention_rules_fire():
    """Seed violations through the pure rule helpers (guards against the
    lint rotting into a silent always-pass)."""
    assert not _lint._NAME_RE.match("NoDots")
    assert not _lint._NAME_RE.match("two.dots.deep")
    assert not _lint._NAME_RE.match("Caps.bad_total")
    assert _lint._NAME_RE.match("serving.shed_total")
    assert _lint._UNIT_SUFFIX["counter"] == "_total"
    assert _lint._UNIT_SUFFIX["histogram"] == "_seconds"


def test_registered_names_match_runtime_registry():
    """Every name the scanner found must be importable-time registered in
    the default registry (and vice versa for package modules that were
    imported) — the lint reads source, the registry is runtime truth."""
    # import every package module in which the scanner found a
    # registration, so that its module-level registrations have run
    # whatever this file runs after
    import importlib
    from analytics_zoo_tpu.common import metrics

    found = _lint.registrations()[0]
    for sites in found.values():
        for where, _kind in sites:
            path = where.rsplit(":", 1)[0]
            if path.startswith("analytics_zoo_tpu/"):
                importlib.import_module(path[:-len(".py")].replace("/", "."))
    runtime = set(metrics.default_registry().snapshot())
    # a name registered only outside the package (bench.py) exists once
    # that script runs
    scanned = {name for name, sites in found.items()
               if any(w.startswith("analytics_zoo_tpu/") for w, _ in sites)}
    missing = scanned - runtime
    assert not missing, (
        f"scanned registrations never ran (dead module-level code?): "
        f"{sorted(missing)}")


def test_documented_set_is_closed():
    """docs/observability.md documents every registered metric."""
    assert _lint.undocumented(_lint.registrations()[0]) == []
