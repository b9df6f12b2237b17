"""``minicpm_sala.longdoc_closed`` rehearsed on the CPU at a tiny size (4
layers, hidden 64, a selection that really drops blocks), untraced and
traced, through ``perfbench/run.py`` as ``test_rehearsal.py`` does for the
four older cells; the cell's committed limits against the readings they
were set from."""
import copy
import json
import os

import numpy as np
import pytest

from perfbench.harness import check, plant, spec

NAME = "minicpm_sala.longdoc_closed"
LIMITS = {"served_logit_gap_max": 2e-5}


def tiny_sala_cell():
    cfg = copy.deepcopy(spec.load_json(
        spec.ROOT, "perfbench/configs/minicpm_sala.json"))
    cfg.update(
        vocab_size=97, hidden_size=64, intermediate_size=128, head_dim=16,
        num_attention_heads=4, num_key_value_heads=1, lightning_nh=4,
        lightning_nkv=4, lightning_head_dim=16, num_hidden_layers=4,
        mixer_types=["minicpm4", "lightning-attn", "lightning-attn",
                     "lightning-attn"],
        dim_model_base=16, n_positions=256, param_dtype="float32",
        sparse_attention=dict(kernel_size=8, kernel_stride=4, block_size=16,
                              init_blocks=1, window_size=32, topk=2,
                              dense_len=96))
    cfg["serving"].update(slots=3, kv_pages=3 * 16 + 1, kv_page_len=16,
                          prefill_chunk=64, max_new_tokens=24)
    mix = spec.load_like("traffic", "longdoc_closed")
    mix.update(grid=4, ramp_seconds=1, trace_seconds=1, compare_requests=3,
               callers=3, prompt_len={"quantiles": [[0, 100], [1, 200]]},
               output_len={"quantiles": [[0, 6], [1, 24]]})
    return spec.Cell(NAME, config=cfg, traffic=mix, limits=dict(LIMITS))


def _body(line):
    return {k: v for k, v in line.items() if k != "_stderr"}


@pytest.mark.parametrize("traced", [0, 1])
def test_longdoc_cell_rehearsal(run_cell, traced, tmp_path, monkeypatch):
    cell = tiny_sala_cell()
    # a queue and a trace directory of this file's own: another worker may
    # be rehearsing an older cell in the checkout's at the same moment
    monkeypatch.setattr(spec, "ROOT", str(tmp_path))
    body = _body(run_cell(cell, trace=traced, seconds=2))
    assert body["correct"] is True and body["failed"] == 0
    assert body["attempted"] >= 3
    assert list(body)[-1] == "compared"
    want = {m["name"] for m in (cell.per_layer() if traced
                                else cell.end_to_end())}
    assert set(body["metrics"]) <= want
    if traced:
        # what the host's clock, spans and counters give reads on the CPU
        # too; scopes and programs by name need the chip's trace
        assert {"slots_busy_mean.longdoc", "decode_step_ms.longdoc",
                "device_idle_pct.longdoc", "serve_mfu_pct.longdoc",
                "decode_steps_per_chunk.longdoc",
                "sparse_positions_read_mean.longdoc",
                "serve_host_ms_per_step.longdoc",
                "queue_host_ms_per_step.longdoc",
                "compiles_in_window.longdoc"} <= set(body["metrics"])
        read = body["metrics"]["sparse_positions_read_mean.longdoc"]["value"]
        assert read == 6 * 16  # 1 initial + 3 local + 2 chosen blocks of 16
        assert body["metrics"]["decode_steps_per_chunk.longdoc"]["value"] > 0
        assert body["device"]["busy_s"] > 0
    else:
        assert set(body["metrics"]) == {"out_tokens_per_s", "setup_s"}
        assert all(m["value"] > 0 for m in body["metrics"].values())
    assert body["compared"]["served_logit_gap_max"]["value"] <= 2e-5
    assert not os.path.exists(os.path.join(str(tmp_path), ".perfbench_queue",
                                           NAME))


def test_the_control_at_fp8_is_not_correct_at_the_tiny_size():
    from perfbench.harness import traffic
    cell = tiny_sala_cell()
    ref, cfg = cell.reference(), cell.config
    weights = ref.init_weights(cfg, 7)
    tokens = np.asarray(traffic.rng(7, 3).integers(1, cfg["vocab_size"],
                                                   (2, 160)), np.int32)
    mask = np.zeros(tokens.shape, bool)
    mask[:, -25:-1] = True
    sound = ref.gaps_below_best(cfg, weights, tokens, ref.first_choice(
        cfg, weights, tokens, "highest"))
    assert float(np.max(sound)) == 0.0
    assert plant.serve_control(cfg, ref, weights, tokens, mask) > \
        LIMITS["served_logit_gap_max"]


def test_the_cell_holds_the_served_cells_number():
    assert set(spec.Cell(NAME).limits()) == set(LIMITS)


def test_recorded_readings_lie_on_their_side_of_the_limit():
    """Beside ``test_rehearsal.py``'s check of every cell's readings: the
    limit lies between the largest sound reading and the smallest control,
    with the more room above the sound one."""
    limit = spec.Cell(NAME).limits()["served_logit_gap_max"]
    path = os.path.join(spec.BENCH_DIR, "limits", "readings", NAME + ".jsonl")
    rows = [json.loads(l) for l in open(path) if l.strip()]
    sound = [r["numbers"]["served_logit_gap_max"] for r in rows
             if r["kind"] == "program"]
    control = [r["numbers"]["served_logit_gap_max"] for r in rows
               if r["kind"] == "control"]
    assert len(sound) >= 6 and len(control) >= 3
    assert max(sound) < limit < min(control)
    for value in sound + control:
        ok, _ = check.verdict({"served_logit_gap_max": value},
                              {"served_logit_gap_max": limit})
        assert ok == (value in sound)
