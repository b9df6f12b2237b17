"""Consolidated zoolint suite: one session-scoped run of every pass over
the repo (self-clean assertion, including suppression hygiene), seeded
violations per new pass on throwaway project trees, the suppression
machinery end to end, the discovery-vs-legacy acceptance diff for the
jit-boundary pass, and the CLI contract.

The ported passes (hot-path-sync / metric-names / fault-sites) keep their
seeded fixtures in their legacy test files, which now load the shared
``analytics_zoo_tpu.lint`` modules through the ``scripts/check_*.py``
shims — so every entry point in the whole suite shares ONE parsed AST
index per process.
"""
import importlib.util
import os

import pytest

from analytics_zoo_tpu.lint import core, runner
from analytics_zoo_tpu.lint.core import (Finding, Project, run_passes,
                                         UNUSED_SUPPRESSION_ID)
from analytics_zoo_tpu.lint.passes import hot_path, jit_boundary

REPO_ROOT = core.REPO_ROOT

ALL_PASS_IDS = {"config-keys", "event-names", "fault-sites",
                "hot-path-sync", "jit-host-sync", "metric-names",
                "monotonic-clock", "retry-discipline"}


def _seed(tmp_path, files):
    """A throwaway project tree: ``<tmp>/analytics_zoo_tpu/<name>``."""
    pkg = tmp_path / "analytics_zoo_tpu"
    pkg.mkdir(exist_ok=True)
    (pkg / "__init__.py").write_text("")
    for name, text in files.items():
        (pkg / name).write_text(text)
    return Project(root=str(tmp_path))


# -- the repo itself ----------------------------------------------------------

@pytest.fixture(scope="session")
def repo_result():
    """All passes, once per session, over the shared project index."""
    return run_passes(core.get_project())


@pytest.fixture(scope="session")
def discovery():
    """One jit-boundary discovery over the repo, shared by the tests that
    inspect it (the pass itself re-discovers inside repo_result)."""
    return jit_boundary.discover(core.get_project())


def test_repo_is_zoolint_clean(repo_result):
    assert repo_result.clean, "\n" + "\n".join(
        f.text() for f in repo_result.findings)


def test_every_pass_ran(repo_result):
    assert set(repo_result.pass_ids) == ALL_PASS_IDS


def test_live_waivers_actually_engage(repo_result):
    """The repo carries deliberate suppressions (profiling fence, gated
    loss sync, wall_clock, ...); each must have matched a real finding —
    hygiene already fails stale ones, this guards the other direction."""
    assert repo_result.suppressed, (
        "expected live suppressions to waive real findings")
    assert {f.pass_id for f in repo_result.suppressed} <= ALL_PASS_IDS


def test_shared_parse_cache_is_one_per_process():
    p = core.get_project()
    assert core.get_project() is p
    est = os.path.join(REPO_ROOT, "analytics_zoo_tpu", "estimator",
                       "estimator.py")
    assert p.source(est) is p.source(est)


def test_legacy_shims_share_the_lint_modules():
    """scripts/check_hot_path_syncs.py must be a shim over the shared
    pass module — same function objects, same project cache."""
    script = os.path.join(REPO_ROOT, "scripts", "check_hot_path_syncs.py")
    spec = importlib.util.spec_from_file_location("_shim_probe", script)
    shim = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(shim)
    assert shim.check is hot_path.check
    assert shim._CHECKS is hot_path._CHECKS


# -- seeded violations: jit-host-sync ----------------------------------------

def test_jit_host_sync_catches_seeded_violations(tmp_path):
    proj = _seed(tmp_path, {"model.py": (
        "import time\n"
        "\n"
        "import jax\n"
        "import jax.numpy as jnp\n"
        "\n"
        "\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    t = time.time()\n"
        "    v = float(x.sum())\n"
        "    while v > 0:\n"
        "        v -= 1.0\n"
        "    return _inner(x) + t\n"
        "\n"
        "\n"
        "def _inner(x):\n"
        "    total = 0.0\n"
        "    for i in range(x.shape[0]):\n"
        "        total = total + x[i]\n"
        "    return total\n")})
    res = run_passes(proj, ids=["jit-host-sync"])
    by_line = {f.line: f.message for f in res.findings}
    assert "host clock read time.time()" in by_line[9]
    assert "float()" in by_line[10]
    assert "while loop" in by_line[11]
    # _inner is only reachable FROM the jitted root: transitive discovery
    assert "per-element Python loop" in by_line[18]


def test_jit_host_sync_clean_module_stays_clean(tmp_path):
    proj = _seed(tmp_path, {"model.py": (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "\n"
        "\n"
        "@jax.jit\n"
        "def step(params, x):\n"
        "    for name, p in sorted(params.items()):\n"
        "        x = x + p\n"
        "    return jnp.exp(x)\n")})
    res = run_passes(proj, ids=["jit-host-sync"])
    assert res.clean, "\n".join(f.text() for f in res.findings)


# -- seeded violations: config-keys ------------------------------------------

def test_config_keys_catches_seeded_drift(tmp_path):
    proj = _seed(tmp_path, {"conf.py": (
        "def global_config():\n"
        "    return None\n"
        "\n"
        "\n"
        "cfg = global_config()\n"
        "cfg.register('orphan.key', 1, 'registered, never read')\n"
        "cfg.register('BadKey', 2, 'breaks the convention')\n"
        "cfg.get('never.registered')\n")})
    res = run_passes(proj, ids=["config-keys"])
    msgs = "\n".join(f.message for f in res.findings)
    assert "'orphan.key' is registered but never read" in msgs
    assert "'BadKey' breaks the dotted 'section.name' convention" in msgs
    assert "'never.registered' read at" in msgs
    assert "no row in docs/configuration.md" in msgs


def test_config_keys_ignores_plain_dict_gets(tmp_path):
    """Receivers are resolved, not guessed: ``d.get("x.y")`` on an
    ordinary dict never counts as a config read."""
    proj = _seed(tmp_path, {"conf.py": (
        "d = {}\n"
        "v = d.get('looks.like_a_key')\n")})
    res = run_passes(proj, ids=["config-keys"])
    assert res.clean, "\n".join(f.text() for f in res.findings)


# -- seeded violations: monotonic-clock --------------------------------------

def test_monotonic_clock_catches_seeded_wall_clock(tmp_path):
    proj = _seed(tmp_path, {"sched.py": (
        "import time\n"
        "\n"
        "\n"
        "def wait():\n"
        "    deadline = time.time() + 5\n"
        "    lease = time.time_ns()\n"
        "    t0 = time.monotonic()\n"
        "    return deadline, lease, t0\n")})
    res = run_passes(proj, ids=["monotonic-clock"])
    assert [f.line for f in res.findings] == [5, 6]
    assert all("wall-clock" in f.message for f in res.findings)


def test_monotonic_clock_catches_mixed_domain_arithmetic(tmp_path):
    """The lease/heartbeat bug class: one expression subtracting (or
    comparing) a monotonic read against a wall_clock() stamp is flagged
    even though each read is legitimate on its own; same-domain
    arithmetic on either clock stays clean."""
    proj = _seed(tmp_path, {"lease.py": (
        "import time\n"
        "from analytics_zoo_tpu.common.utils import wall_clock\n"
        "\n"
        "\n"
        "def age_wrong():\n"
        "    return time.monotonic() - wall_clock()\n"
        "\n"
        "\n"
        "def expired_wrong(stamp_s):\n"
        "    return wall_clock() + stamp_s < time.perf_counter()\n"
        "\n"
        "\n"
        "def age_right(observed_mono):\n"
        "    return time.monotonic() - observed_mono\n"
        "\n"
        "\n"
        "def stamp_right():\n"
        "    return wall_clock() + 30.0\n")})
    res = run_passes(proj, ids=["monotonic-clock"])
    assert [f.line for f in res.findings] == [6, 10]
    assert all("mixes monotonic- and wall-clock" in f.message
               for f in res.findings)


# -- seeded violations: retry-discipline -------------------------------------

def test_retry_discipline_catches_seeded_storms(tmp_path):
    proj = _seed(tmp_path, {"rpc.py": (
        "import time\n"
        "\n"
        "\n"
        "def poll(fetch):\n"
        "    for _ in range(5):\n"
        "        try:\n"
        "            return fetch()\n"
        "        except OSError:\n"
        "            time.sleep(0.05)\n"
        "    raise TimeoutError\n"
        "\n"
        "\n"
        "def forever(fetch):\n"
        "    while True:\n"
        "        try:\n"
        "            fetch()\n"
        "        except OSError:\n"
        "            pass\n")})
    res = run_passes(proj, ids=["retry-discipline"])
    by_line = {f.line: f.message for f in res.findings}
    assert "fixed (unjittered) retry delay" in by_line[9]
    assert "unbounded `while True` retry loop" in by_line[14]
    assert len(res.findings) == 2


def test_retry_discipline_accepts_jittered_bounded_retries(tmp_path):
    """The reference shape — computed full-jitter backoff inside a
    bounded loop, and a ``while True`` that escapes via return/raise —
    stays clean; so does a sleep whose delay is computed, not constant."""
    proj = _seed(tmp_path, {"rpc.py": (
        "import random\n"
        "import time\n"
        "\n"
        "\n"
        "def call(fetch, attempts=3, base=0.05):\n"
        "    for attempt in range(attempts):\n"
        "        try:\n"
        "            return fetch()\n"
        "        except OSError:\n"
        "            time.sleep(random.uniform(0.0, base * 2 ** attempt))\n"
        "    raise TimeoutError\n"
        "\n"
        "\n"
        "def drain(fetch):\n"
        "    while True:\n"
        "        try:\n"
        "            return fetch()\n"
        "        except KeyboardInterrupt:\n"
        "            raise\n")})
    res = run_passes(proj, ids=["retry-discipline"])
    assert res.clean, "\n".join(f.text() for f in res.findings)


# -- seeded violations: event-names ------------------------------------------

def test_event_names_catches_seeded_violations(tmp_path):
    """Every rule of the event-type contract fires on a seeded tree:
    non-literal name, duplicate registration, convention breakage, and
    (with no docs in the tree) undocumented types."""
    proj = _seed(tmp_path, {"emitter.py": (
        "from analytics_zoo_tpu.ops import events\n"
        "\n"
        "_NAME = 'ops' + '.computed'\n"
        "_E_DYN = events.event_type(_NAME, 'computed name')\n"
        "_E_A = events.event_type('serving.thing', 'owned here')\n"
        "_E_B = events.event_type('serving.thing', 'owned here too')\n"
        "_E_BAD = events.event_type('NoDotsOrCase', 'breaks convention')\n")})
    res = run_passes(proj, ids=["event-names"])
    msgs = "\n".join(f.message for f in res.findings)
    assert "event type name must be one string literal" in msgs
    assert "'serving.thing' registered at 2 sites" in msgs
    assert "'NoDotsOrCase'" in msgs and "subsystem.noun" in msgs
    assert "registered but undocumented" in msgs


def test_event_names_resolves_receivers_not_strings(tmp_path):
    """Only events-module aliases count: ``event_type`` on an unrelated
    object is not a registration, and an ``ops_events`` alias is."""
    proj = _seed(tmp_path, {"emitter.py": (
        "from analytics_zoo_tpu.ops import events as ops_events\n"
        "\n"
        "\n"
        "class _Factory:\n"
        "    def event_type(self, name, help=''):\n"
        "        return name\n"
        "\n"
        "\n"
        "factory = _Factory()\n"
        "factory.event_type('not.a_registration')\n"
        "_E = ops_events.event_type('fleet.something', 'real one')\n")})
    import analytics_zoo_tpu.lint.passes.event_names as event_names
    regs, bad = event_names.registrations(proj)
    assert bad == []
    assert set(regs) == {"fleet.something"}


def test_event_names_scanner_sees_known_transitions():
    """The repo scanner must find the load-bearing event types — a
    scanner matching nothing would always pass."""
    import analytics_zoo_tpu.lint.passes.event_names as event_names
    regs, bad = event_names.registrations()
    assert bad == []
    for expected in ("serving.brownout_rung", "fleet.breaker",
                     "cluster.restart", "ops.alert", "ops.incident",
                     "fault.fired"):
        assert expected in regs, expected


def test_event_names_documented_set_is_closed():
    """docs/observability.md's event table covers every registered
    type, and the doc mentions no phantom checks (lint self-clean rides
    repo_result; this pins the docs half specifically)."""
    import analytics_zoo_tpu.lint.passes.event_names as event_names
    assert event_names.undocumented(event_names.registrations()[0]) == []


def test_event_names_matches_runtime_registry():
    """Source-scanned types must match runtime registration once the
    emitting modules are imported (fault.fired registers lazily on first
    fire, so it is exempt from the runtime side)."""
    import importlib
    import analytics_zoo_tpu.lint.passes.event_names as event_names
    from analytics_zoo_tpu.ops import events

    # import every package module in which the scanner found a
    # registration, whatever this file runs after; a type registered only
    # outside the package (bench.py's burst) exists once that script runs
    found = event_names.registrations()[0]
    for sites in found.values():
        for where in sites:
            path = where.rsplit(":", 1)[0]
            if path.startswith("analytics_zoo_tpu/"):
                importlib.import_module(path[:-len(".py")].replace("/", "."))
    runtime = set(events.registered_types())
    scanned = {name for name, sites in found.items()
               if any(w.startswith("analytics_zoo_tpu/") for w in sites)}
    missing = scanned - runtime - {"fault.fired"}
    assert not missing, (
        f"scanned event_type registrations never ran (dead module-level "
        f"code?): {sorted(missing)}")


# -- suppression machinery ----------------------------------------------------

def test_suppression_same_line(tmp_path):
    proj = _seed(tmp_path, {"s.py": (
        "import time\n"
        "t = time.time()  # zoolint: disable=monotonic-clock — test stamp\n")})
    res = run_passes(proj, ids=["monotonic-clock"])
    assert res.clean and len(res.suppressed) == 1


def test_suppression_standalone_line_above(tmp_path):
    proj = _seed(tmp_path, {"s.py": (
        "import time\n"
        "# zoolint: disable=monotonic-clock — cross-process stamp\n"
        "t = time.time()\n")})
    res = run_passes(proj, ids=["monotonic-clock"])
    assert res.clean and len(res.suppressed) == 1


def test_suppression_file_level(tmp_path):
    proj = _seed(tmp_path, {"s.py": (
        "# zoolint: disable-file=monotonic-clock — wall-clock glue module\n"
        "import time\n"
        "a = time.time()\n"
        "b = time.time_ns()\n")})
    res = run_passes(proj, ids=["monotonic-clock"])
    assert res.clean and len(res.suppressed) == 2


def test_stale_waiver_is_flagged(tmp_path):
    proj = _seed(tmp_path, {"s.py": (
        "# zoolint: disable=monotonic-clock — nothing here anymore\n"
        "x = 1\n")})
    res = run_passes(proj, ids=["monotonic-clock"])
    assert [f.pass_id for f in res.findings] == [UNUSED_SUPPRESSION_ID]
    assert "unused suppression" in res.findings[0].message


def test_waiver_without_justification_is_flagged(tmp_path):
    proj = _seed(tmp_path, {"s.py": (
        "import time\n"
        "t = time.time()  # zoolint: disable=monotonic-clock\n")})
    res = run_passes(proj, ids=["monotonic-clock"])
    # the finding is waived, but the bare waiver itself is a finding
    assert len(res.suppressed) == 1
    assert [f.pass_id for f in res.findings] == [UNUSED_SUPPRESSION_ID]
    assert "no justification" in res.findings[0].message


def test_waiver_naming_unknown_pass_is_flagged(tmp_path):
    proj = _seed(tmp_path, {"s.py": (
        "x = 1  # zoolint: disable=not-a-pass — typo'd id\n")})
    res = run_passes(proj, ids=["monotonic-clock"])
    assert [f.pass_id for f in res.findings] == [UNUSED_SUPPRESSION_ID]
    assert "unknown pass" in res.findings[0].message


def test_waiver_for_unselected_pass_not_reported_stale(tmp_path):
    """Running a pass subset must not flag waivers belonging to passes
    that did not run — they had no chance to match."""
    proj = _seed(tmp_path, {"s.py": (
        "# zoolint: disable=jit-host-sync — belongs to a pass not run here\n"
        "x = 1\n")})
    res = run_passes(proj, ids=["monotonic-clock"])
    assert res.clean, "\n".join(f.text() for f in res.findings)


def test_waiver_inside_string_literal_is_inert(tmp_path):
    """Suppressions are comment tokens; a fixture string seeding one must
    not waive anything."""
    proj = _seed(tmp_path, {"s.py": (
        "import time\n"
        'doc = "t = time.time()  # zoolint: disable=monotonic-clock — no"\n'
        "t = time.time()\n")})
    res = run_passes(proj, ids=["monotonic-clock"])
    assert [f.line for f in res.findings] == [3]
    assert not res.suppressed


# -- acceptance: discovery vs the legacy hand-listed table -------------------

#: the legacy rows that are host-side staging (data-plane iterator cores,
#: batch gathers, the DeviceFeed producer) or one-shot allocation
#: initializers — host code by design, so trace/dispatch discovery cannot
#: and should not find them; they stay policed via the hot-path table seed.
HOST_STAGING_ROWS = {
    "_cached_batches", "_gather", "_produce", "_transformed_batches",
    "eval_iterator", "init_paged_pool", "init_slot_cache",
    "masked_eval_batches", "train_iterator",
    # XShard ETL engine bodies: host-side numpy/pandas shuffle kernels in
    # forked workers — never traced, so jit discovery can't see them
    "_bucket_order", "_exchange_task", "_filter_task", "_gather_dest",
    "_groupby_task", "_handoff_task", "_join_match", "_join_task",
    "_mix64", "_stack_into", "_take_cols_into",
    # fleet router placement scoring: host-side numpy over instance-gauge
    # arrays — never traced, so jit discovery can't see it
    "_score_instances",
}

#: fused embedding kernel rows (ops/embedding_kernels.py): the pallas
#: bodies only trace inside ``pl.pallas_call`` (not a discovery root —
#: the hot-path table polices them instead), and the wrappers are
#: reached through the config-gated ``_fused_kernels()`` module handle,
#: an indirection static call-graph resolution cannot follow. Sourced
#: from the pass's own tuples so the sets cannot drift apart.
EMBED_KERNEL_ROWS = (set(hot_path.EMBED_KERNEL_BODIES)
                     | set(hot_path.EMBED_KERNEL_WRAPPERS))

#: the paged decode kernel's pallas body (ops/decode.py), for the same
#: reason; its wrapper ``paged_decode_context`` is discovered.
PAGED_KERNEL_ROWS = set(hot_path.PAGED_KERNEL_BODIES)


def test_jit_discovery_covers_legacy_table(discovery):
    disc = discovery
    legacy = hot_path.policed_functions()
    # the full policed surface (auto + seeded) covers every legacy row
    missing = legacy - disc.discovered_names()
    assert not missing, f"policed surface lost legacy rows: {sorted(missing)}"
    # every DEVICE-side legacy row is discovered automatically — no seed:
    # embedding shard_map bodies, slot/paged KV ops, decode/LM/server jits
    auto = disc.traced_names() | disc.dispatch_names()
    assert HOST_STAGING_ROWS <= legacy, "exemption list drifted from table"
    assert EMBED_KERNEL_ROWS <= legacy, "exemption list drifted from table"
    assert PAGED_KERNEL_ROWS <= legacy, "exemption list drifted from table"
    not_auto = (legacy - HOST_STAGING_ROWS - EMBED_KERNEL_ROWS
                - PAGED_KERNEL_ROWS) - auto
    assert "paged_decode_context" in auto
    assert not not_auto, (
        f"device-side legacy rows no longer auto-discovered: "
        f"{sorted(not_auto)}")


def test_discovery_traverses_the_package(discovery):
    """Discovery must keep finding a real traced surface — a resolver
    regression that silently found nothing would pass every clean test."""
    disc = discovery
    assert len(disc.traced) >= 100, len(disc.traced)
    assert len(disc.dispatch) >= 15, len(disc.dispatch)
    for name in ("_lookup_body", "paged_attention", "spec_accept_greedy"):
        assert name in disc.traced_names(), name


# -- CLI ----------------------------------------------------------------------

def test_cli_list_exits_zero(capsys):
    assert runner.main(["--list"]) == 0
    out = capsys.readouterr().out
    for pid in ALL_PASS_IDS:
        assert pid in out


def test_cli_clean_repo_exits_zero(capsys):
    """A pass subset keeps this cheap; full-repo cleanliness across ALL
    passes is repo_result's session-scoped assertion."""
    assert runner.main(["--pass", "hot-path-sync",
                        "--pass", "monotonic-clock"]) == 0
    err = capsys.readouterr().err
    assert "zoolint: clean" in err


def test_cli_unknown_pass_exits_two(capsys):
    assert runner.main(["--pass", "bogus"]) == 2
    assert "unknown pass id" in capsys.readouterr().err


def test_cli_findings_exit_one_and_github_format(tmp_path, monkeypatch,
                                                 capsys):
    proj = _seed(tmp_path, {"s.py": "import time\nt = time.time()\n"})
    monkeypatch.setattr(core, "_project", proj)
    assert runner.main(["--pass", "monotonic-clock"]) == 1
    out = capsys.readouterr().out
    assert "[monotonic-clock]" in out
    assert runner.main(["--pass", "monotonic-clock",
                        "--format", "github"]) == 1
    out = capsys.readouterr().out
    assert "::error file=" in out and "title=zoolint/monotonic-clock" in out


def test_finding_formats():
    f = Finding(os.path.join(REPO_ROOT, "x.py"), 3, "demo",
                "50% of\nthis", "do the fix")
    assert f.text() == "x.py:3: [demo] 50% of\nthis  [fix: do the fix]"
    g = f.github()
    assert g.startswith("::error file=x.py,line=3,title=zoolint/demo::")
    assert "50%25 of%0Athis" in g
