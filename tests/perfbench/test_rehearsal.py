"""``perfbench/run.py`` rehearsed on the CPU at a tiny size, one cell of each
kind: the shape of the last line, untraced and traced; no run without a
chip; the control and the planted faults come out as not correct."""
import os
import subprocess
import sys

import numpy as np
import pytest

from conftest import ROOT, TRAIN_LIMITS, tiny_serve_cell, tiny_train_cell

from perfbench.harness import check, plant

LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _strip(line):
    """The result line without what the fixture added; its numbers must
    also be the last lines of standard error."""
    body = {k: v for k, v in line.items() if k != "_stderr"}
    last = line["_stderr"].strip().splitlines()[-len(body["compared"]):]
    assert all(l.startswith("perfbench correct: ") for l in last), last
    return body


@pytest.mark.parametrize("traced", [0, 1])
def test_train_cell_rehearsal(run_cell, traced):
    cell = tiny_train_cell()
    line = run_cell(cell, trace=traced)
    assert line["correct"] is True
    body = _strip(line)
    assert LINE_KEYS <= set(body) and list(body)[-1] == "compared"
    if traced:
        assert body["device"]["busy_s"] > 0
        assert body["device"]["window_s"] > body["device"]["busy_s"] * 0.5
        assert len(body["breakdown"]["device_ops"]) <= 10
        assert "step_ms_p50.train" in body["metrics"]
        assert "train_mfu_pct" in body["metrics"]
        assert "allreduce_exposed_ms_per_step" not in body["metrics"]
    else:
        assert set(body["metrics"]) == {"train_tokens_per_s", "setup_s"}
        assert body["metrics"]["train_tokens_per_s"]["value"] > 0
        assert body["attempted"] % 4 == 0  # whole epochs of four steps
    assert set(body["compared"]) == set(TRAIN_LIMITS)


@pytest.mark.parametrize("name,traced", [
    ("gpt2_small.doc_closed", 0), ("gpt2_small.doc_closed", 1),
    ("gpt2_small.chat_open", 0), ("gpt2_small.chat_open", 1)])
def test_generate_cell_rehearsal(run_cell, name, traced):
    cell = tiny_serve_cell(name)
    body = _strip(run_cell(cell, trace=traced, seconds=2))
    assert body["correct"] is True and body["failed"] == 0
    assert body["attempted"] >= 3
    assert list(body)[-1] == "compared"
    want = {m["name"] for m in (cell.per_layer() if traced
                                else cell.end_to_end())}
    assert set(body["metrics"]) <= want
    if traced:
        suffix = ".doc" if "doc" in name else ".chat"
        assert {"slots_busy_mean" + suffix, "decode_step_ms" + suffix,
                "device_idle_pct" + suffix} <= set(body["metrics"])
        assert body["device"]["busy_s"] > 0
        spans = [g[0] for g in body["breakdown"]["idle_gaps"]]
        assert any(s.startswith(("queue.", "profile.serving", "host:"))
                   for s in spans)
    else:
        assert set(body["metrics"]) == want
        assert all(m["value"] > 0 for m in body["metrics"].values())
    assert body["compared"]["served_logit_gap_max"]["value"] <= 0.002
    assert not os.path.exists(os.path.join(ROOT, ".perfbench_queue", name))


def test_no_run_without_a_chip():
    """Outside the rehearsal ``run.py`` takes no CPU: exit code other than
    0, and no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"),
         "--workload", "bert_base.glue_s128.1chip", "--seed", "1",
         "--seconds", "1", "--trace", "0"], env=env, cwd=ROOT,
        capture_output=True, text=True, timeout=300)
    assert done.returncode != 0
    assert "no TPU" in done.stderr
    assert not [l for l in done.stdout.splitlines() if l.startswith("{")]


def test_unknown_workload_fails():
    from perfbench import run
    with pytest.raises(SystemExit):
        run.main(["--workload", "nope", "--seed", "1", "--seconds", "1"])


# -- the control, and the faults that a cell can have ---------------------------

def _train_numbers(**planted):
    """The numbers that the tiny cell holds to a limit, with the reference in
    the program's place."""
    numbers = plant.train_reference_in_place(tiny_train_cell(), 5, **planted)
    return {k: v for k, v in numbers.items() if k in TRAIN_LIMITS}


def test_train_control_at_fp8_is_not_correct():
    sound = check.verdict(_train_numbers(), TRAIN_LIMITS)
    assert sound[0], sound[1]
    assert max(v for _, v, _ in sound[1]) < 1e-6
    control = check.verdict(_train_numbers(precision="fp8"), TRAIN_LIMITS)
    assert not control[0], control[1]


def test_exchange_between_chips_left_out_is_not_correct():
    numbers = _train_numbers(shard_rows=2)  # one of four chips' rows
    assert not check.verdict(numbers, TRAIN_LIMITS)[0], numbers


@pytest.mark.parametrize("fault", [plant.unchanged_state, plant.half_batch])
def test_train_faults_are_not_correct(run_cell, fault):
    body = _strip(run_cell(tiny_train_cell(), fault=fault))
    assert body["correct"] is False
    over = [n for n, row in body["compared"].items()
            if row["value"] > row["limit"]]
    assert over, body["compared"]


def test_an_altered_token_is_not_correct(run_cell):
    cell = tiny_serve_cell("gpt2_small.doc_closed")
    body = _strip(run_cell(cell, seconds=2, fault=plant.altered_token))
    assert body["correct"] is False
    row = body["compared"]["served_logit_gap_max"]
    assert row["value"] > row["limit"]


def test_serve_control_reads_the_runs_own_sample(run_cell):
    """The tools read the control on the prompts and tokens that the run
    itself compared."""
    cell, control = tiny_serve_cell("gpt2_small.doc_closed"), {}
    body = _strip(run_cell(cell, seconds=2, control=control))
    assert body["correct"] is True
    assert control["served_tokens_compared"] >= 100
    assert control["served_logit_gap_max"] >= 0.0


def test_serve_control_at_fp8_is_not_correct():
    """The reference at float8 in the program's place: the token it puts
    first lies further below the reference's best than the limit allows,
    and the reference's own first choice lies nowhere below it."""
    from perfbench.harness import traffic
    cell = tiny_serve_cell("gpt2_small.doc_closed")
    ref, cfg = cell.reference(), cell.config
    weights = ref.init_weights(cfg, 7)
    tokens = np.asarray(traffic.rng(7, 3).integers(0, cfg["vocab_size"],
                                                   (8, 96)), np.int32)
    mask = np.ones(tokens.shape, bool)
    sound = ref.gaps_below_best(cfg, weights, tokens, ref.first_choice(
        cfg, weights, tokens, "highest"))
    assert float(np.max(sound)) == 0.0
    assert plant.serve_control(cfg, ref, weights, tokens, mask) > \
        cell.limits()["served_logit_gap_max"]


def test_a_request_that_never_ends_is_a_problem_not_a_number():
    ok, table = check.verdict({"served_logit_gap_max": 0.0},
                              {"served_logit_gap_max": 0.05},
                              problems=["request 3: no terminal"])
    assert not ok
    assert check.verdict({"x": 0.0}, {})[0] is False  # no limit, no pass
    assert check.verdict({"x": float("nan")}, {"x": 1.0})[0] is False


# -- the cells' committed limits, against the readings they were set from -------

def _cells():
    from perfbench.harness import spec
    return [w["name"] for w in spec.benchmark()["workloads"]]


@pytest.mark.parametrize("name", _cells())
def test_recorded_readings_get_the_verdict_they_must(name):
    """``limits/readings/<cell>.jsonl`` keeps what ``tools/readings.py`` and
    the full sets read on the chip at the cell's own size. At the committed
    limits every sound run is correct, and every control and planted fault
    is not: a limit moved above a control's reading fails here."""
    import json
    from perfbench.harness import spec
    limits = spec.Cell(name).limits()
    path = os.path.join(spec.BENCH_DIR, "limits", "readings", name + ".jsonl")
    rows = [json.loads(l) for l in open(path) if l.strip()]
    kinds = {r["kind"] for r in rows}
    assert "program" in kinds and "control" in kinds
    for row in rows:
        held = {k: v for k, v in row["numbers"].items() if k in limits}
        ok, table = check.verdict(held, limits)
        assert ok == (row["kind"] == "program"), (row["kind"], row["seed"],
                                                  table)


@pytest.mark.parametrize("name", _cells())
def test_the_rehearsal_holds_the_numbers_the_cell_holds(name):
    from conftest import SERVE_LIMITS
    from perfbench.harness import spec
    cell = spec.Cell(name)
    tiny = TRAIN_LIMITS if cell.kind == "train" else SERVE_LIMITS
    assert set(cell.limits()) == set(tiny)


def test_a_like_file_takes_the_other_files_keys():
    from perfbench.harness import spec
    one = spec.Cell("bert_base.glue_s128.1chip")
    four = spec.Cell("bert_base.glue_s128.dp4")
    assert four.limits() == one.limits()
    assert four.traffic["what"] != one.traffic["what"]
    assert {k: v for k, v in four.traffic.items() if k != "what"} == \
        {k: v for k, v in one.traffic.items() if k != "what"}
