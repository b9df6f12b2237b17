"""The traced CPU rehearsals of ``test_rehearsal.py`` again, for what PR 25
adds: the metrics that read the program's own spans are present in each
kind of cell, the gaps are named by the program's spans, and the metrics
that need scope names from a device trace are left out on the CPU (its
trace carries none) without a fault."""
import os

import pytest

from conftest import tiny_serve_cell, tiny_train_cell

from perfbench.harness import spec

SCOPED = {"kv_move_share_pct", "attn_block_share_pct", "optimizer_share_pct",
          "attn_short_roofline", "unscoped_share_pct"}


@pytest.fixture
def elsewhere(monkeypatch, tmp_path):
    """Moves a run's queue and trace directories out of the checkout, once
    the cell is built: ``test_rehearsal.py`` runs the same tiny cells in
    the checkout's, and under ``--dist loadfile`` the two files run at the
    same time."""
    def move():
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", os.environ.get(
            "JAX_COMPILATION_CACHE_DIR",
            os.path.join(spec.ROOT, ".jax_cache")))
        monkeypatch.setattr(spec, "ROOT", str(tmp_path))
    return move


def _metrics(run_cell, elsewhere, cell, **kwargs):
    elsewhere()
    line = run_cell(cell, trace=1, **kwargs)
    assert line["correct"] is True
    return line


def test_train_cell_reads_the_programs_feed_span(run_cell, elsewhere):
    line = _metrics(run_cell, elsewhere, tiny_train_cell())
    got = line["metrics"]
    assert got["feed_wait_ms_per_step.train"]["value"] >= 0.0
    assert got["compiles_in_window.train"] == {"value": 0.0,
                                               "unit": "compiles"}
    # the twin from outside wraps the same call, so it is never shorter
    assert got["data_wait_ms_per_step.train"]["value"] >= \
        got["feed_wait_ms_per_step.train"]["value"]
    assert not [n for n in got if n.split(".")[0] in SCOPED]


def test_four_chip_train_cell_lists_its_own_new_metrics():
    cell = tiny_train_cell("bert_base.glue_s128.dp4")
    names = {m["name"] for m in cell.per_layer()}
    assert {"attn_short_roofline.dp4", "feed_wait_ms_per_step.train",
            "attn_block_share_pct.train", "optimizer_share_pct.train",
            "unscoped_share_pct.train", "compiles_in_window.train"} <= names
    assert "attn_short_roofline.train" not in names


def test_chat_cell_reads_the_serve_loops_spans(run_cell, elsewhere):
    line = _metrics(run_cell, elsewhere,
                    tiny_serve_cell("gpt2_small.chat_open"),
                    seconds=2)
    got = line["metrics"]
    for name in ("serve_host_ms_per_step.chat", "serve_post_ms_per_step.chat",
                 "queue_wait_p95_ms.chat", "first_token_p95_ms.chat"):
        assert got[name]["value"] > 0.0, name
    assert got["serve_post_ms_per_step.chat"]["value"] < \
        got["serve_host_ms_per_step.chat"]["value"]
    assert got["compiles_in_window.chat"]["value"] == 0.0
    # a request's first token cannot come before its claim
    assert got["ttft_p95_ms.chat"]["value"] >= \
        got["first_token_p95_ms.chat"]["value"]
    assert not [n for n in got if n.split(".")[0] in SCOPED]


def test_doc_cell_reads_the_serve_loops_spans(run_cell, elsewhere):
    line = _metrics(run_cell, elsewhere,
                    tiny_serve_cell("gpt2_small.doc_closed"),
                    seconds=2)
    got = line["metrics"]
    assert got["serve_host_ms_per_step.doc"]["value"] > \
        got["serve_post_ms_per_step.doc"]["value"] > 0.0
    assert got["compiles_in_window.doc"]["value"] == 0.0
    assert "queue_wait_p95_ms.chat" not in got
    gaps = [g[0] for g in line["breakdown"]["idle_gaps"]]
    assert "host:no_span" not in gaps[:1]
    assert any(g.startswith(("serve.", "queue.")) for g in gaps), gaps
