"""Test harness: simulate an 8-device TPU mesh on CPU.

The reference tests distributed semantics on a single host with multi-partition
``local[n]`` Spark masters (SURVEY.md §4). The TPU equivalent is an 8-device
virtual CPU mesh via ``xla_force_host_platform_device_count``, set before jax
initializes.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
# the suite (and the children it starts, which inherit this) compiles
# thousands of small CPU programs once each: keep JAX's persistent cache,
# which the package otherwise always wires (common/context.py), out of it
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import inspect  # noqa: E402

import pytest  # noqa: E402

#: wall-seconds of the tier-1 870s budget (ROADMAP verify command) that
#: non-slow multi-process tests may collectively declare — the rest
#: belongs to the single-process suite. Breaching this fails COLLECTION,
#: so a new pod test that would blow the CI budget is caught before it
#: runs, not after CI times out.
_POD_BUDGET_CAP_S = 420.0

#: names whose presence in a test's source means it spawns worker
#: subprocesses and must carry @pytest.mark.pod(budget_s=...)
_POD_SPAWNERS = ("PodLauncher", "run_pod(", "ElasticSupervisor",
                 "FleetSupervisor")


def pytest_collection_modifyitems(config, items):
    total, unbudgeted, unmarked = 0.0, [], []
    for item in items:
        mark = item.get_closest_marker("pod")
        if mark is None:
            fn = getattr(item, "function", None)
            try:
                src = inspect.getsource(fn) if fn else ""
            except (OSError, TypeError):
                src = ""
            if any(s in src for s in _POD_SPAWNERS):
                unmarked.append(item.nodeid)
            continue
        if item.get_closest_marker("slow") is not None:
            continue  # tier-2: outside the 870s budget
        budget = float(mark.kwargs.get("budget_s", 0.0))
        if budget <= 0:
            unbudgeted.append(item.nodeid)
        total += budget
    problems = []
    if unmarked:
        problems.append(
            f"multi-process tests must declare a wall budget with "
            f"@pytest.mark.pod(budget_s=...): {unmarked}")
    if unbudgeted:
        problems.append(
            f"pod marker without a positive budget_s: {unbudgeted}")
    if total > _POD_BUDGET_CAP_S:
        problems.append(
            f"non-slow pod tests declare {total:.0f}s of wall budget, "
            f"over the {_POD_BUDGET_CAP_S:.0f}s cap — mark the heaviest "
            f"soaks slow or shrink them")
    if problems:
        raise pytest.UsageError("; ".join(problems))


@pytest.fixture()
def ctx():
    from analytics_zoo_tpu.common.context import init_tpu_context, reset_context
    reset_context()
    context = init_tpu_context(force_reinit=True)
    yield context
    reset_context()
