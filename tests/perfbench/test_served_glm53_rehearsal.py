"""``glm_5_3_flash.longgen_closed`` rehearsed on the CPU at a tiny size (a
KDA layer with a dense feed-forward, then a sparse latent layer and two KDA
layers with experts; hidden 64, 4 KDA heads of 16, an indexer over blocks
of 4 with top-16 that the prompts pass, 16 experts with 4 a token of which 8
are held, 4 residual streams), untraced and traced, through
``perfbench/run.py`` as the ``glm5`` rehearsal does for its cell; the cell's
committed limits against the readings they were set from."""
import copy
import json
import os

import numpy as np
import pytest

from perfbench.harness import check, plant, spec

NAME = "glm_5_3_flash.longgen_closed"
LIMITS = {"served_logit_gap_max": 2e-5}


def tiny_longgen_cell():
    cfg = copy.deepcopy(spec.load_json(
        spec.ROOT, "perfbench/configs/glm_5_3_flash.json"))
    cfg.update(
        vocab_size=97, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_attention_heads=4,
        num_key_value_heads=4, q_lora_rank=32, kv_lora_rank=16,
        qk_nope_head_dim=16, qk_head_dim=16, v_head_dim=16, index_n_heads=2,
        index_head_dim=8, index_topk=16, n_routed_experts=8,
        n_routed_experts_published=16, held_experts=list(range(8)),
        num_experts_per_tok=4, num_hidden_layers=4, first_k_dense_replace=1,
        layer_types=["linear_attention", "deepseek_sparse_attention",
                     "linear_attention", "linear_attention"],
        mlp_layer_types=["dense", "sparse", "sparse", "sparse"],
        linear_attn_config=dict(cfg["linear_attn_config"], num_heads=4,
                                head_dim=16),
        n_positions=128, param_dtype="float32", router_bias_spread=0.02)
    cfg["serving"].update(slots=3, kv_pages=3 * 16 + 1, kv_page_len=8,
                          prefill_chunk=32, max_new_tokens=24)
    mix = spec.load_like("traffic", "longgen_closed")
    # a short traced stretch: on the CPU every operation is a busy interval
    # of its own, and ``readers.module_share_pct`` pairs each with each run
    mix.update(grid=4, ramp_seconds=1, trace_seconds=0.4, compare_requests=3,
               callers=3, prompt_len={"quantiles": [[0, 20], [1, 100]]},
               output_len={"quantiles": [[0, 6], [1, 24]]})
    return spec.Cell(NAME, config=cfg, traffic=mix, limits=dict(LIMITS))


def _body(line):
    return {k: v for k, v in line.items() if k != "_stderr"}


@pytest.mark.parametrize("traced", [0, 1])
def test_longgen_cell_rehearsal(run_cell, traced, tmp_path, monkeypatch):
    cell = tiny_longgen_cell()
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
        assert 0 < got["serve_mfu_pct.longgen"] < 100
        assert body["device"]["busy_s"] > 0
    else:
        assert set(body["metrics"]) == {"out_tokens_per_s", "setup_s"}
        assert all(m["value"] > 0 for m in body["metrics"].values())
    assert body["compared"]["served_logit_gap_max"]["value"] <= 2e-5
    assert not os.path.exists(os.path.join(str(tmp_path), ".perfbench_queue",
                                           NAME))


def test_the_control_at_fp8_is_not_correct_at_the_tiny_size():
    from perfbench.harness import traffic
    cell = tiny_longgen_cell()
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


def test_a_compared_gap_is_the_mean_over_its_run():
    """One turned token among 300 served ones reads 1/128 of its gap over
    its run of 128 and nothing over the next run of 172; a position that
    was not served (a nought in ``chosen``) keeps its own gap."""
    ref = spec.Cell(NAME).reference()
    gaps = np.zeros(400, np.float32)
    gaps[[10, 350]] = 1.0
    compared = np.zeros(400, bool)
    compared[0:300] = True
    got = ref.block_means(gaps, compared, 128)
    np.testing.assert_array_equal(got[:128], np.float32(1 / 128))
    np.testing.assert_array_equal(got[128:300], 0.0)
    assert got[350] == 1.0
    # fewer than a run: one mean over them all
    assert ref.block_means(gaps, np.arange(400) < 50, 128)[0] == 0.02


def test_a_served_nought_is_held_to_its_own_gap(monkeypatch):
    """``gaps_below_best`` averages the positions whose chosen token is not
    nought, and leaves a served nought its single gap: a program that
    serves noughts is not hidden in a mean."""
    ref = spec.Cell(NAME).reference()
    raw = np.zeros((1, 64), np.float32)
    raw[0, 40:] = 0.5
    monkeypatch.setattr(ref, "token_gaps", lambda *a: raw.copy())
    monkeypatch.setattr(ref.G, "_served", lambda cfg, row: (64, 30, 34))
    tokens = np.ones((1, 64), np.int32)
    chosen = np.zeros((1, 64), np.int32)
    chosen[0, 30:60] = 7
    chosen[0, 45] = 0
    got = ref.gaps_below_best({}, None, tokens, chosen)[0]
    # 29 positions averaged (30..59 less 45): 19 of them read 0.5
    np.testing.assert_allclose(got[30:45], 19 * 0.5 / 29, rtol=1e-6)
    assert got[45] == 0.5 and got[62] == 0.5


def test_the_reference_imports_nothing_of_the_program():
    import perfbench.references.glm53_lm as ref
    source = open(ref.__file__).read()
    assert "analytics_zoo_tpu" not in source.replace(
        "Nothing\nof the program", "")


def test_the_configuration_states_the_deployment_and_the_pool():
    cfg = spec.load_json(spec.ROOT, "perfbench/configs/glm_5_3_flash.json")
    row = next(c for c in spec.benchmark()["configs"]
               if c["name"] == "glm_5_3_flash")
    assert sorted(row["reduced"]) == sorted(cfg["reduced"])
    assert cfg["num_hidden_layers"] == 5 and cfg["first_k_dense_replace"] == 1
    assert cfg["layer_types"] == ["linear_attention",
                                  "deepseek_sparse_attention"] \
        + ["linear_attention"] * 3
    assert cfg["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert cfg["n_routed_experts"] == len(cfg["held_experts"]) == 36
    assert cfg["num_nextn_predict_layers"] == 0
    assert "8 chips share each layer" in cfg["deployment"]
    serving = cfg["serving"]
    assert serving["kv_pages"] == serving["slots"] * (
        cfg["n_positions"] // serving["kv_page_len"]) + 1
    mix = spec.load_like("traffic", "longgen_closed")
    longest = mix["prompt_len"]["quantiles"][-1][1] \
        + mix["output_len"]["quantiles"][-1][1]
    assert longest == cfg["n_positions"]
    assert mix["callers"] == serving["slots"] == 64
    assert serving["max_new_tokens"] == mix["output_len"]["quantiles"][-1][1]


def test_the_cell_holds_the_served_cells_number():
    assert set(spec.Cell(NAME).limits()) == set(LIMITS)


def test_recorded_readings_lie_on_their_side_of_the_limit():
    """The limit lies between the largest sound reading and the smallest
    control, with the more room above the sound one (as a ratio, as the
    other cells' limits are set), and every reading gets its verdict
    again."""
    limit = spec.Cell(NAME).limits()["served_logit_gap_max"]
    path = os.path.join(spec.BENCH_DIR, "limits", "readings", NAME + ".jsonl")
    rows = [json.loads(line) for line in open(path) if line.strip()]
    sound = [r["numbers"]["served_logit_gap_max"] for r in rows
             if r["kind"] == "program"]
    control = [r["numbers"]["served_logit_gap_max"] for r in rows
               if r["kind"] == "control"]
    assert len(sound) >= 6 and len(control) >= 3
    assert max(sound) < limit < min(control)
    assert limit / max(sound) >= min(control) / limit
    for value in sound + control:
        ok, _ = check.verdict({"served_logit_gap_max": value},
                              {"served_logit_gap_max": limit})
        assert ok == (value in sound)
