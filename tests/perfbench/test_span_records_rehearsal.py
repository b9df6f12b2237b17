"""The traced CPU rehearsals of ``gpt2_small.doc_closed`` and
``minicpm_sala.longdoc_closed`` again, for what PR 35 adds: the eight
metrics that read the program's whole span records are reported, the five
of the iteration add up to the step with the spans that already had
readers, and ``tools/gaps_by_lane.py`` names the gaps by the loop's lane
alone."""
import json
import os

import pytest

from conftest import tiny_serve_cell
from test_rehearsal_sala import tiny_sala_cell

from perfbench import run
from perfbench.harness import records, spec
from perfbench.tools import gaps_by_lane

LOOP = ("loop_claim_ms_per_step.doc", "loop_join_ms_per_step.doc",
        "loop_dispatch_ms_per_step.doc", "loop_evict_ms_per_step.doc",
        "loop_self_ms_per_step.doc")
PREFILL = ("prefill_wait_p95_ms.longdoc", "prefill_pending_mean.longdoc",
           "prefill_chunk_fill_pct.longdoc")


@pytest.fixture
def traced(monkeypatch, tmp_path, capsys):
    """One traced run of a tiny cell through ``run.main``, its queue and
    trace directories out of the checkout; returns the result line and the
    run's context."""
    def drive(cell, seconds=2):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", os.environ.get(
            "JAX_COMPILATION_CACHE_DIR",
            os.path.join(spec.ROOT, ".jax_cache")))
        monkeypatch.setattr(spec, "ROOT", str(tmp_path))
        probe = {}
        line = run.main(["--workload", cell.name, "--seed", "4000000123",
                         "--seconds", str(seconds), "--trace", "1"],
                        cell=cell, allow_cpu=True, probe=probe)
        capsys.readouterr()
        assert line["correct"] is True
        # what the tool prints of the run is one JSON object
        printed = json.loads(json.dumps(gaps_by_lane.report(
            line, probe["ctx"], probe["values"])))
        assert printed["loop_lane"].endswith("-loop")
        assert printed["records"] > 0 and printed["iteration_ms_per_step"]
        return line, probe["ctx"], printed
    return drive


def test_doc_cell_reports_the_iterations_parts(traced):
    line, ctx, printed = traced(tiny_serve_cell("gpt2_small.doc_closed"))
    got = {k: v["value"] for k, v in line["metrics"].items()}
    for name in LOOP:
        assert got[name] >= 0.0, name
        assert line["metrics"][name]["unit"] == "ms"
    assert got["loop_dispatch_ms_per_step.doc"] > 0.0
    assert got["loop_claim_ms_per_step.doc"] > 0.0
    # the identity the issue asks for, with what no metric reads named
    step = records.ms_per_step(ctx, "serve.admit") - \
        got["loop_claim_ms_per_step.doc"] - got["loop_join_ms_per_step.doc"]
    total = (sum(got[name] for name in LOOP) + step
             + got["serve_post_ms_per_step.doc"]
             + got["fetch_wait_ms_per_step.doc"]
             + sum(records.ms_per_step(ctx, name) or 0.0 for name in (
                 "serve.expire", "serve.health", "serve.prepare")))
    whole = gaps_by_lane.iteration_table(ctx)
    # (the two older readers take a span by its start in the stretch, not
    # by its step's: the stretch's last iteration may count on one side)
    assert total == pytest.approx(whole["serve.step"], abs=0.02)
    assert whole["self"] == pytest.approx(got["loop_self_ms_per_step.doc"])
    assert sum(whole["self, by the child before it"].values()) == \
        pytest.approx(whole["self"])
    assert got["loop_self_ms_per_step.doc"] < 0.5 * whole["serve.step"]
    # the older reader of the same spans agrees with the records
    assert got["serve_host_ms_per_step.doc"] == pytest.approx(
        whole["serve.step"] - got["fetch_wait_ms_per_step.doc"], abs=0.02)
    # gaps by the loop's lane: no write of the publisher's, no stretch of
    # a request's life
    recs = records.of(ctx)
    lane = records.lane_of(recs)
    assert lane.endswith("-loop") and lane == printed["loop_lane"]
    named = gaps_by_lane.gaps_by_lane(ctx, recs)
    assert named and sum(v for _, v in named) == pytest.approx(
        ctx["trace"]["window_s"] - ctx["trace"]["busy_s"], rel=1e-6)
    mine = {r.name for r in recs if r.lane == lane}
    assert {name for name, _ in named} <= mine | {"host:no_span",
                                                  "between_operations"}
    assert not {"serve.put_result", "serve.publish_lag", "serve.queue_wait",
                "queue.put_result"} & {name for name, _ in named}
    assert "prefill_wait_p95_ms.longdoc" not in got
    assert printed["prefill"]["waits"] == 0  # no prompt is fed in chunks
    assert printed["idle_gaps_by_lane"] == [list(g) for g in named]


def test_longdoc_cell_reports_the_prefill_turn(traced):
    cell = tiny_sala_cell()
    line, ctx, printed = traced(cell)
    assert printed["prefill"]["waits"] > 0
    assert printed["prefill"]["chunk_fill_pct"] == pytest.approx(
        line["metrics"]["prefill_chunk_fill_pct.longdoc"]["value"])
    got = {k: v["value"] for k, v in line["metrics"].items()}
    for name in PREFILL:
        assert name in got, name
    assert got["prefill_wait_p95_ms.longdoc"] > 0.0
    assert 0.0 < got["prefill_chunk_fill_pct.longdoc"] <= 100.0
    slots = cell.config["serving"]["slots"]
    assert 0.0 <= got["prefill_pending_mean.longdoc"] \
        <= slots - got["slots_busy_mean.longdoc"] + 1e-9
    # a prompt waits for its turn no longer than for its first token
    recs = records.of(ctx)
    firsts = {r.request: r for r in recs if r.name == "serve.first_token"}
    waits = records.starting_in(recs, "serve.prefill_wait", ctx["t0"],
                                ctx["t1"])
    assert waits
    for w in waits:
        if w.request in firsts:
            assert w.start == pytest.approx(firsts[w.request].start)
            assert w.seconds < firsts[w.request].seconds
    assert not [n for n in LOOP if n in got]
