"""zoolint pass ``metric-names``: registry names stay canonical.

Ported from ``scripts/check_metric_names.py`` (now a thin shim over this
module). The telemetry plane (``analytics_zoo_tpu/common/metrics.py``)
only stays queryable if names don't rot: a metric registered twice makes
dashboards ambiguous, an off-convention name breaks every ``subsystem.*``
query, and an undocumented metric is invisible to whoever writes the
alerts. Rules:

1. every registration call (``metrics.counter(...)`` / ``.gauge(...)`` /
   ``.histogram(...)`` on a metrics-module alias) passes a string LITERAL
   name (a computed name defeats both this lint and grep);
2. every metric name is registered exactly ONCE across the codebase — one
   name, one owning module (re-registration elsewhere would silently
   alias series);
3. names follow the ``subsystem.noun_unit`` convention
   (lower_snake, one dot), counters end in ``_total``, histograms in
   ``_seconds`` (all our histograms observe durations), and gauges carry
   a unit suffix (``_seconds``/``_bytes``/``_ratio``/``_depth``) unless
   allow-listed as genuinely unitless;
4. every registered metric is documented in ``docs/observability.md``
   (the metric table is the operator's scrape vocabulary).
"""
from __future__ import annotations

import ast
import os
import re
import sys
from typing import Dict, List, Tuple

from ..core import (Finding, LintPass, Project, REPO_ROOT, get_project,
                    register_pass)

_PKG = os.path.join(REPO_ROOT, "analytics_zoo_tpu")
_DOCS = os.path.join(REPO_ROOT, "docs", "observability.md")

#: common/metrics.py itself is excluded (its internal plumbing calls the
#: same method names on ``self``/fresh registries)
_EXCLUDE = (os.path.join("common", "metrics.py"),)

_KINDS = ("counter", "gauge", "histogram")
_NAME_RE = re.compile(r"^[a-z][a-z0-9]*\.[a-z][a-z0-9_]*$")
_UNIT_SUFFIX = {"counter": "_total", "histogram": "_seconds"}

#: gauges must say what they measure; any of these suffixes qualifies
_GAUGE_UNIT_SUFFIXES = ("_seconds", "_bytes", "_ratio", "_depth")
#: gauges that are genuinely unitless: live request/slot counts, the
#: info-style constant-1 build gauge (labels carry the payload), and the
#: enumerated state machines (brownout rung, breaker state)
_GAUGE_UNITLESS_OK = {"serving.in_flight", "serving.slots_occupied",
                      "serving.kv_pages_free", "build.info",
                      "fleet.instances_alive", "fleet.desired_instances",
                      "cluster.leases_alive", "serving.brownout_level",
                      "fleet.breaker_state", "serving.state_slots_in_use",
                      "serving.publish_backlog", "serving.kv_pages_in_use"}
#: histograms of a count, not of a duration: exempt from the suffix rule
_HISTOGRAM_UNITLESS_OK = {"serving.sparse_positions_read",
                           "serving.paged_pages_read",
                           "serving.moe_experts_touched",
                           "serving.moe_expert_load",
                           "serving.dsa_positions_scored",
                           "serving.dsa_blocks_scored"}


def _is_registration(node: ast.Call) -> bool:
    f = node.func
    return (isinstance(f, ast.Attribute) and f.attr in _KINDS
            and isinstance(f.value, ast.Name)
            and (f.value.id == "metrics" or f.value.id.endswith("_metrics")))


def registrations() -> Tuple[Dict[str, List[Tuple[str, str]]],
                             List[Tuple[str, int, str]]]:
    """``{name: [(file:line, kind), ...]}`` over all scanned files, plus
    violations for non-literal name arguments."""
    project = get_project()
    regs: Dict[str, List[Tuple[str, str]]] = {}
    bad: List[Tuple[str, int, str]] = []
    files = project.package_files()
    if os.path.exists(project.bench_file()):
        files = files + [project.bench_file()]
    for path in sorted(files):
        rel = os.path.relpath(path, REPO_ROOT)
        if any(rel.endswith(e) for e in _EXCLUDE):
            continue
        tree = project.ast_for(path)
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call) and _is_registration(node)):
                continue
            where = f"{rel}:{node.lineno}"
            if (not node.args
                    or not isinstance(node.args[0], ast.Constant)
                    or not isinstance(node.args[0].value, str)):
                bad.append((path, node.lineno,
                            "metric name must be one string literal"))
                continue
            regs.setdefault(node.args[0].value, []).append(
                (where, node.func.attr))
    return regs, bad


def undocumented(names) -> List[str]:
    """Registered names with no `` `name` `` mention in the metric docs."""
    try:
        with open(_DOCS) as fh:
            text = fh.read()
    except OSError:
        return sorted(names)
    return sorted(n for n in names if f"`{n}`" not in text)


def _locate(regs: Dict[str, List[Tuple[str, str]]], name: str
            ) -> Tuple[str, int]:
    where = regs[name][0][0]
    rel, _, line = where.rpartition(":")
    return os.path.join(REPO_ROOT, rel), int(line)


def check() -> List[str]:
    """Human-readable violations; empty = clean."""
    return [f.message for f in findings()]


def findings() -> List[Finding]:
    regs, bad = registrations()
    out: List[Finding] = []
    for p, line, what in bad:
        out.append(Finding(p, line, MetricNamesPass.id,
                           f"{os.path.relpath(p, REPO_ROOT)}:{line}: {what}",
                           "pass the metric name as one string literal"))
    for name, places in sorted(regs.items()):
        path, line = _locate(regs, name)
        if len(places) > 1:
            out.append(Finding(
                path, line, MetricNamesPass.id,
                f"metric {name!r} registered at {len(places)} sites "
                f"({', '.join(w for w, _ in places)}); each name must be "
                f"registered exactly once",
                "keep one owning module per metric"))
        kind = places[0][1]
        if not _NAME_RE.match(name):
            out.append(Finding(
                path, line, MetricNamesPass.id,
                f"metric {name!r} ({places[0][0]}) breaks the "
                f"'subsystem.noun_unit' convention (lower_snake, one dot)",
                "rename to subsystem.noun_unit"))
        suffix = _UNIT_SUFFIX.get(kind)
        if (suffix and not name.endswith(suffix)
                and name not in _HISTOGRAM_UNITLESS_OK):
            out.append(Finding(
                path, line, MetricNamesPass.id,
                f"{kind} {name!r} ({places[0][0]}) must end in "
                f"'{suffix}'", f"rename with the {suffix} suffix"))
        if (kind == "gauge" and name not in _GAUGE_UNITLESS_OK
                and not name.endswith(_GAUGE_UNIT_SUFFIXES)):
            out.append(Finding(
                path, line, MetricNamesPass.id,
                f"gauge {name!r} ({places[0][0]}) must end in one of "
                f"{'/'.join(_GAUGE_UNIT_SUFFIXES)} or be allow-listed in "
                f"_GAUGE_UNITLESS_OK",
                "add a unit suffix or allow-list a genuinely unitless "
                "gauge"))
    for name in undocumented(regs):
        path, line = _locate(regs, name)
        out.append(Finding(
            path, line, MetricNamesPass.id,
            f"metric {name!r} is registered but undocumented — add a row "
            f"to the metric table in docs/observability.md",
            "document every metric an operator can scrape"))
    return out


@register_pass
class MetricNamesPass(LintPass):
    id = "metric-names"
    title = "metrics registry naming/uniqueness/documentation contract"
    rationale = (
        "telemetry only stays queryable if names stay literal, unique, "
        "canonical and documented — drift is invisible to behavioral "
        "tests")

    def run(self, project: Project) -> List[Finding]:
        return findings()


def main() -> int:
    problems = check()
    if not problems:
        print(f"metric-name lint: clean ({len(registrations()[0])} metrics,"
              f" all literal, unique, canonical and documented)")
        return 0
    for p in problems:
        print(p, file=sys.stderr)
    return 1
