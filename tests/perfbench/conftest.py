"""Tiny presets for the rehearsal of ``perfbench/run.py`` on the CPU. The
cells keep the published widths; these sizes exist in the tests only."""
import copy
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench.harness import spec  # noqa: E402

# the cells' own set of numbers (``limits/<cell>.json``), at limits that fit
# the tiny size on the CPU
TRAIN_LIMITS = {"loss_gap_step2": 0.02, "loss_gap_step3": 0.02,
                "grad_norm_gap": 0.025, "grad_norm_gap_median": 0.025,
                "change_norm_gap": 0.5, "change_norm_gap_median": 0.05,
                "grad_diff_median": 0.02}
SERVE_LIMITS = {"served_logit_gap_max": 0.002}


def tiny_train_cell(name="bert_base.glue_s128.1chip"):
    cfg = spec.load_json(spec.ROOT, "perfbench/configs/bert_base.json")
    cfg.update(vocab_size=64, hidden_size=32, num_hidden_layers=2,
               num_attention_heads=2, intermediate_size=64,
               max_position_embeddings=16)
    mix = spec.load_like("traffic", name.split(".", 1)[1])
    mix.update(seq_len=16, batch_per_chip=8, steps_per_epoch=4, grid=8,
               reference_rows=4, lengths={"quantiles": [[0, 2], [1, 16]]})
    return spec.Cell(name, config=cfg, traffic=mix,
                     limits=dict(TRAIN_LIMITS))


def tiny_serve_cell(name):
    cfg = copy.deepcopy(spec.load_json(spec.ROOT,
                                       "perfbench/configs/gpt2_small.json"))
    cfg.update(vocab_size=97, n_embd=32, n_layer=2, n_head=2, n_inner=64,
               n_positions=128, n_ctx=128)
    cfg["serving"].update(slots=4, kv_pages=4 * 8 + 1)
    mix = spec.load_like("traffic", name.split(".", 1)[1])
    mix.update(grid=4, ramp_seconds=1, trace_seconds=1, compare_requests=16,
               prompt_len={"quantiles": [[0, 5], [1, 40]]},
               output_len={"quantiles": [[0, 6], [1, 30]]})
    if mix["loop"] == "closed":
        mix["callers"] = 3
    else:
        mix["rate_per_s"] = 5.0
    return spec.Cell(name, config=cfg, traffic=mix,
                     limits=dict(SERVE_LIMITS))


@pytest.fixture
def run_cell(capsys):
    """Drive ``run.main`` on the CPU for a tiny cell; returns the parsed
    last line of standard output."""
    import json

    from perfbench import run

    def drive(cell, trace=0, seconds=1.5, seed=4000000123, **planted):
        run.main(["--workload", cell.name, "--seed", str(seed), "--seconds",
                  str(seconds), "--trace", str(trace)], cell=cell,
                 allow_cpu=True, **planted)
        out = capsys.readouterr()
        line = json.loads(out.out.strip().splitlines()[-1])
        line["_stderr"] = out.err
        return line
    return drive
