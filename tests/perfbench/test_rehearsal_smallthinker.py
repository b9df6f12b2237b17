"""``smallthinker_21b.mixed_closed`` rehearsed on the CPU at a tiny size (4
layers ``[full, window, window, window]``, hidden 64, 8 experts with 2 a
token, a window of 32 that the prompts pass), untraced and traced, through
``perfbench/run.py`` as ``test_rehearsal_sala.py`` does for its cell; the
cell's committed limits against the readings they were set from."""
import copy
import json
import os

import numpy as np
import pytest

from perfbench.harness import check, plant, spec

NAME = "smallthinker_21b.mixed_closed"
LIMITS = {"served_logit_gap_max": 2e-5}


def tiny_mixed_cell():
    cfg = copy.deepcopy(spec.load_json(
        spec.ROOT, "perfbench/configs/smallthinker_21b.json"))
    cfg.update(
        vocab_size=97, hidden_size=64, moe_ffn_hidden_size=32, head_dim=16,
        num_attention_heads=4, num_key_value_heads=2,
        moe_num_primary_experts=8, moe_num_active_primary_experts=2,
        sliding_window_size=32, num_hidden_layers=4,
        sliding_window_layout=[0, 1, 1, 1], rope_layout=[0, 1, 1, 1],
        n_positions=128, param_dtype="float32")
    cfg["serving"].update(slots=3, kv_pages=3 * 16 + 1, kv_page_len=8,
                          prefill_chunk=32, max_new_tokens=24)
    mix = spec.load_like("traffic", "mixed_closed")
    mix.update(grid=4, ramp_seconds=1, trace_seconds=1, compare_requests=3,
               callers=3, prompt_len={"quantiles": [[0, 10], [1, 100]]},
               output_len={"quantiles": [[0, 6], [1, 24]]})
    return spec.Cell(NAME, config=cfg, traffic=mix, limits=dict(LIMITS))


def _body(line):
    return {k: v for k, v in line.items() if k != "_stderr"}


@pytest.mark.parametrize("traced", [0, 1])
def test_mixed_cell_rehearsal(run_cell, traced, tmp_path, monkeypatch):
    cell = tiny_mixed_cell()
    # a queue and a trace directory of this file's own: another worker may
    # be rehearsing another cell in the checkout's at the same moment
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
        got = {k: v["value"] for k, v in body["metrics"].items()}
        assert {"slots_busy_mean.mixed", "decode_step_ms.mixed",
                "device_idle_pct.mixed", "serve_mfu_pct.mixed",
                "decode_steps_per_chunk.mixed",
                "moe_experts_touched_mean.mixed",
                "moe_load_max_over_mean.mixed",
                "kv_pages_window_mean.mixed",
                "serve_host_ms_per_step.mixed",
                "serve_post_ms_per_step.mixed", "ttft_p95_ms.mixed",
                "tpot_p95_ms.mixed", "compiles_in_window.mixed"} <= set(got)
        # 2 of 8 experts a token, at most 3 resident streams
        assert 2.0 <= got["moe_experts_touched_mean.mixed"] <= 6.0
        assert 8 / 6 <= got["moe_load_max_over_mean.mixed"] <= 4.0
        # a window of 32 lies on at most 5 pages of 8; the prompt being
        # fed holds up to 4 more
        assert 0 < got["kv_pages_window_mean.mixed"] <= 5 + 4
        assert got["decode_steps_per_chunk.mixed"] > 0
        assert body["device"]["busy_s"] > 0
    else:
        assert set(body["metrics"]) == {"out_tokens_per_s", "setup_s"}
        assert all(m["value"] > 0 for m in body["metrics"].values())
    assert body["compared"]["served_logit_gap_max"]["value"] <= 2e-5
    assert not os.path.exists(os.path.join(str(tmp_path), ".perfbench_queue",
                                           NAME))


def test_the_control_at_fp8_is_not_correct_at_the_tiny_size():
    from perfbench.harness import traffic
    cell = tiny_mixed_cell()
    ref, cfg = cell.reference(), cell.config
    weights = ref.init_weights(cfg, 7)
    tokens = np.asarray(traffic.rng(7, 3).integers(1, cfg["vocab_size"],
                                                   (2, 120)), np.int32)
    mask = np.zeros(tokens.shape, bool)
    mask[:, -25:-1] = True
    sound = ref.gaps_below_best(cfg, weights, tokens, ref.first_choice(
        cfg, weights, tokens, "highest"))
    assert float(np.max(sound)) == 0.0
    assert plant.serve_control(cfg, ref, weights, tokens, mask) > \
        LIMITS["served_logit_gap_max"]


def test_the_reference_imports_nothing_of_the_program():
    import perfbench.references.smallthinker_lm as ref
    source = open(ref.__file__).read()
    assert "analytics_zoo_tpu" not in source.replace(
        "Nothing of the program", "")


def test_the_configuration_holds_the_sources_keys():
    """Every number of the catalog entry under the same key, but for the
    three keys in ``reduced``."""
    cfg = spec.load_json(spec.ROOT, "perfbench/configs/smallthinker_21b.json")
    published = {
        "head_dim": 128, "hidden_size": 2560,
        "max_position_embeddings": 16384, "moe_ffn_hidden_size": 768,
        "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
        "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
        "num_attention_heads": 28, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1500000,
        "sliding_window_size": 4096, "tie_word_embeddings": False,
        "vocab_size": 151936, "model_name": "smallthinker_21b_instruct"}
    assert {k: cfg[k] for k in published} == published
    assert set(cfg["reduced"]) == {"num_hidden_layers",
                                   "sliding_window_layout", "rope_layout"}
    assert cfg["num_hidden_layers"] == 8
    assert cfg["sliding_window_layout"] == cfg["rope_layout"] \
        == [0, 1, 1, 1] * 2
    row = next(c for c in spec.benchmark()["configs"]
               if c["name"] == "smallthinker_21b")
    assert sorted(row["reduced"]) == sorted(cfg["reduced"])
    serving = cfg["serving"]
    assert serving["kv_pages"] == serving["slots"] * (
        cfg["n_positions"] // serving["kv_page_len"]) + 1
    assert serving["kv_pages_window"] == serving["slots"] * 65 + 32 + 1


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
