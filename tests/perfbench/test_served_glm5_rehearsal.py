"""``glm_5.longctx_closed`` rehearsed on the CPU at a tiny size (1 dense + 3
expert layers, hidden 64, 4 heads of 12 + 4 / 16, an indexer of 2 heads of
8 with top-16 that the prompts pass, 16 experts with 4 a token of which 8
are held), untraced and traced, through ``perfbench/run.py`` as
``test_rehearsal_smallthinker.py`` does for its cell; the cell's committed
limits against the readings they were set from."""
import copy
import json
import os

import numpy as np
import pytest

from perfbench.harness import check, plant, spec

NAME = "glm_5.longctx_closed"
LIMITS = {"served_logit_gap_max": 2e-5}


def tiny_longctx_cell():
    cfg = copy.deepcopy(spec.load_json(
        spec.ROOT, "perfbench/configs/glm_5.json"))
    cfg.update(
        vocab_size=97, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_attention_heads=4,
        num_key_value_heads=4, q_lora_rank=32, kv_lora_rank=16,
        qk_nope_head_dim=12, qk_rope_head_dim=4, qk_head_dim=16,
        v_head_dim=16, index_n_heads=2, index_head_dim=8, index_topk=16,
        n_routed_experts=8, n_routed_experts_published=16,
        held_experts=list(range(8)), num_experts_per_tok=4,
        num_hidden_layers=4, first_k_dense_replace=1, n_positions=128,
        param_dtype="float32", router_bias_spread=0.02)
    cfg["serving"].update(slots=3, kv_pages=3 * 16 + 1, kv_page_len=8,
                          prefill_chunk=32, max_new_tokens=24)
    mix = spec.load_like("traffic", "longctx_closed")
    # a short traced stretch: on the CPU every operation is a busy interval
    # of its own, and ``readers.module_share_pct`` pairs each with each run
    mix.update(grid=4, ramp_seconds=1, trace_seconds=0.4, compare_requests=3,
               callers=3, prompt_len={"quantiles": [[0, 20], [1, 100]]},
               output_len={"quantiles": [[0, 6], [1, 24]]})
    return spec.Cell(NAME, config=cfg, traffic=mix, limits=dict(LIMITS))


def _body(line):
    return {k: v for k, v in line.items() if k != "_stderr"}


@pytest.mark.parametrize("traced", [0, 1])
def test_longctx_cell_rehearsal(run_cell, traced, tmp_path, monkeypatch):
    cell = tiny_longctx_cell()
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
        assert {"slots_busy_mean.longctx", "decode_step_ms.longctx",
                "device_idle_pct.longctx", "serve_mfu_pct.longctx",
                "decode_steps_per_chunk.longctx",
                "moe_experts_touched_mean.longctx",
                "moe_load_max_over_mean.longctx",
                "dsa_positions_read_mean.longctx",
                "dsa_positions_scored_mean.longctx",
                "serve_host_ms_per_step.longctx",
                "serve_post_ms_per_step.longctx",
                "steps_ahead_share_pct.longctx",
                "fetch_wait_ms_per_step.longctx", "ttft_p95_ms.longctx",
                "tpot_p95_ms.longctx", "compiles_in_window.longctx"} \
            <= set(got)
        # 4 of 16 experts a token, 8 held, at most 3 resident streams
        assert 1.0 <= got["moe_experts_touched_mean.longctx"] <= 8.0
        assert got["moe_load_max_over_mean.longctx"] >= 1.0
        # every prompt is over the top-16: a live stream reads 16 positions
        # of the 20 and more that its indexer scored
        assert got["dsa_positions_read_mean.longctx"] == pytest.approx(16.0)
        assert 20 < got["dsa_positions_scored_mean.longctx"] <= 128
        assert got["decode_steps_per_chunk.longctx"] > 0
        assert got["steps_ahead_share_pct.longctx"] > 50
        assert body["device"]["busy_s"] > 0
    else:
        assert set(body["metrics"]) == {"out_tokens_per_s", "setup_s"}
        assert all(m["value"] > 0 for m in body["metrics"].values())
    assert body["compared"]["served_logit_gap_max"]["value"] <= 2e-5
    assert not os.path.exists(os.path.join(str(tmp_path), ".perfbench_queue",
                                           NAME))


def test_the_control_at_fp8_is_not_correct_at_the_tiny_size():
    from perfbench.harness import traffic
    cell = tiny_longctx_cell()
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
    import perfbench.references.glm5_lm as ref
    source = open(ref.__file__).read()
    assert "analytics_zoo_tpu" not in source.replace(
        "Nothing of the program", "")


def test_the_configuration_states_the_deployment_and_the_pool():
    cfg = spec.load_json(spec.ROOT, "perfbench/configs/glm_5.json")
    row = next(c for c in spec.benchmark()["configs"]
               if c["name"] == "glm_5")
    assert sorted(row["reduced"]) == sorted(cfg["reduced"])
    assert cfg["num_hidden_layers"] == 5 and cfg["first_k_dense_replace"] == 1
    assert cfg["n_routed_experts"] == len(cfg["held_experts"]) == 16
    assert cfg["num_nextn_predict_layers"] == 0
    assert "16 chips share each layer" in cfg["deployment"]
    serving = cfg["serving"]
    assert serving["kv_pages"] == serving["slots"] * (
        cfg["n_positions"] // serving["kv_page_len"]) + 1
    mix = spec.load_like("traffic", "longctx_closed")
    longest = mix["prompt_len"]["quantiles"][-1][1] \
        + mix["output_len"]["quantiles"][-1][1]
    assert longest == cfg["n_positions"] == 33280
    assert mix["prompt_len"]["quantiles"][0][1] >= 2 * cfg["index_topk"]
    assert mix["callers"] == serving["slots"] == 12
    assert serving["max_new_tokens"] == mix["output_len"]["quantiles"][-1][1]


def test_the_cell_holds_the_served_cells_number():
    assert set(spec.Cell(NAME).limits()) == set(LIMITS)


def test_recorded_readings_lie_on_their_side_of_the_limit():
    """Beside ``test_rehearsal.py``'s check of every cell's readings: the
    limit lies between the largest sound reading and the smallest control,
    with the more room above the sound one; the sound readings hold every
    stream of one whole plan served once (``kind`` ``stream``), not only
    the three that a run draws."""
    limit = spec.Cell(NAME).limits()["served_logit_gap_max"]
    path = os.path.join(spec.BENCH_DIR, "limits", "readings", NAME + ".jsonl")
    rows = [json.loads(l) for l in open(path) if l.strip()]
    sound = [r["numbers"]["served_logit_gap_max"] for r in rows
             if r["kind"] == "program"]
    control = [r["numbers"]["served_logit_gap_max"] for r in rows
               if r["kind"] == "control"]
    assert len(sound) >= 6 and len(control) >= 3
    assert max(sound) < limit < min(control)
    assert limit - max(sound) >= 0 and min(control) / limit > 1.2
    for value in sound + control:
        ok, _ = check.verdict({"served_logit_gap_max": value},
                              {"served_logit_gap_max": limit})
        assert ok == (value in sound)


def test_every_stream_of_a_run_is_compared_alone(capsys, tmp_path,
                                                 monkeypatch):
    """``tools/streams.py`` at the tiny size: one line a finished stream,
    ramp and tail among them, each held to the reference by itself."""
    import importlib.util
    path = os.path.join(spec.BENCH_DIR, "tools", "streams.py")
    found = importlib.util.spec_from_file_location("perfbench_streams", path)
    tool = importlib.util.module_from_spec(found)
    found.loader.exec_module(tool)
    cell = tiny_longctx_cell()
    monkeypatch.setattr(spec, "ROOT", str(tmp_path))
    tool.main(["--workload", NAME, "--seed", "4000000123", "--seconds", "2",
               "--most", "5"], cell=cell, allow_cpu=True)
    rows = [json.loads(l) for l in capsys.readouterr().out.splitlines()
            if l.startswith("{")]
    assert len(rows) == 5 and len({r["stream"] for r in rows}) == 5
    for r in rows:
        assert r["kind"] == "program" and r["correct"] and not r["over"]
        assert r["numbers"]["served_logit_gap_max"] <= 2e-5
        assert 20 <= r["prompt"] <= 100 and 6 <= r["served"] <= 24
