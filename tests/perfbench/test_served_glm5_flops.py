"""``harness/flops_glm5.py`` against hand counts at GLM-5's published widths
(issue 33's arithmetic among them), and the configuration file against the
catalog's keys."""
import json
import os

import pytest

from perfbench.harness import flops_glm5 as F
from perfbench.harness import spec

CFG = spec.load_json(spec.ROOT, "perfbench/configs/glm_5.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PEAKS = spec.load_json(spec.BENCH_DIR, "peaks.json")["devices"]["TPU v5 lite"]


def test_layer_parameters_are_the_issues_arithmetic():
    # q_a 12.58 M, q_b 33.55, kv_a 3.54, kv_b 14.68, o 100.66: 165.0 M
    assert F.attention_params(CFG) == 6144 * 2048 + 2048 * 64 * 256 \
        + 6144 * 576 + 512 * 64 * 448 + 64 * 256 * 6144 == 165019648
    # index_q 8.39 M, index_k 0.79, index_w 0.20: 9.37 M
    assert F.indexer_params(CFG) == 2048 * 32 * 128 + 6144 * (128 + 32) \
        == 9371648
    assert F.router_params(CFG) == 6144 * 256 == 1572864
    assert F.expert_params(CFG) == F.shared_params(CFG) \
        == 3 * 6144 * 2048 == 37748736
    assert F.dense_ffn_params(CFG) == 3 * 6144 * 12288 == 226492416
    dense = 165019648 + 9371648 + 226492416
    assert F.layer_params(CFG, True) == dense == 400883712    # 0.80 GB
    moe = 165019648 + 9371648 + 1572864 + 37748736 + 16 * 37748736
    assert F.layer_params(CFG, False) == moe == 817692672     # 1.64 GB
    total = dense + 4 * moe + 2 * 19360 * 6144
    assert F.param_count(CFG) == total == 3909550080          # 7.82 GB
    assert F.expert_layers(CFG) == 4


def test_the_cache_is_a_latent_row_and_an_index_key_a_position():
    row = CFG["kv_lora_rank"] + CFG["qk_rope_head_dim"]
    assert row == 576 and (row + CFG["index_head_dim"]) * 2 == 1408
    serving = CFG["serving"]
    width = CFG["n_positions"] // serving["kv_page_len"]
    assert width == 520 and serving["kv_pages"] == 12 * 520 + 1 == 6241
    # the pool's row is 640: 576 in whole tiles of 128 lanes
    pools = 5 * 6241 * 64 * (640 + 128) * 2
    assert 3.0e9 < pools < 3.1e9
    # K and V of 64 heads would be 65,536 B a position a layer
    assert 2 * 64 * 256 * 2 == 65536


def test_forward_flops_count_the_selection_and_the_held_experts():
    assert F.expected_assignments(CFG) == 0.5         # 8 x 16 / 256
    every = 2 * (165019648 + 9371648)
    near = 5 * (every + 2 * 32 * 128 * 1000 + 2 * 64 * 512 * 1000) \
        + 2 * 226492416 + 4 * 2 * (1572864 + 37748736 + 0.5 * 37748736)
    assert F.position_flops(CFG, 1000) == near
    # beyond the top-2048 the indexer still sees every position, attention
    # the 2,048 selected
    far = 5 * (every + 2 * 32 * 128 * 30000 + 2 * 64 * 512 * 2048) \
        + 2 * 226492416 + 4 * 2 * (1572864 + 37748736 + 2 * 37748736)
    assert F.position_flops(CFG, 30000, 2) == far
    assert F.forward_flops(CFG, 10, 30000, False, 2) == 10 * far
    assert F.forward_flops(CFG, 10, 30000, True, 2) == \
        10 * (far + 2 * 6144 * 19360)
    # issue 33's 2.66 GFLOP a token of products; at the mean context of
    # a prompt the scores over it and the selection's attention add a third
    assert F.position_flops(CFG, 0) == pytest.approx(2.66e9, rel=0.01)
    assert F.position_flops(CFG, 6000) == pytest.approx(3.58e9, rel=0.01)


def test_the_rooflines_least_times():
    # the indexer: 12,000 keys of 128 at 2 bytes against 8,192 FLOPs a key
    got = F.index_least_seconds(CFG, 12000, PEAKS)
    assert got == pytest.approx(12000 * 256 / 819e9) \
        and 12000 * 256 / 819e9 > 12000 * 8192 / 197e12
    # the sparse read: 2,048 rows of 576 at 2 bytes against 64 x 1,088 x 2
    # FLOPs a row; a stream under the top-2048 reads what it has
    got = F.attend_least_seconds(CFG, 12000, PEAKS)
    assert got == pytest.approx(2048 * 1152 / 819e9) \
        and 1152 / 819e9 > 64 * 1088 * 2 / 197e12
    assert F.attend_least_seconds(CFG, 700, PEAKS) == \
        pytest.approx(700 * 1152 / 819e9)
    # the experts: 5 touched of the 16 held against 6 assignments
    got = F.experts_least_seconds(CFG, 5, 6, PEAKS)
    assert got == pytest.approx(5 * 37748736 * 2 / 819e9)
    # a chunk's 1,024 assignments are still bound by the 16 experts' bytes
    assert F.experts_least_seconds(CFG, 16, 1024, PEAKS) == \
        pytest.approx(16 * 37748736 * 2 / 819e9)
    assert F.experts_least_seconds(CFG, 16, 8192, PEAKS) == \
        pytest.approx(8192 * 2 * 37748736 / 197e12)


def test_the_file_holds_the_catalogs_keys_but_for_what_it_reduced():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    rows = [json.loads(l) for l in open(CATALOG) if l.strip()]
    entry = next(r for r in rows if r["name"] == "GLM-5")
    bench = next(c for c in spec.benchmark()["configs"]
                 if c["name"] == "glm_5")
    assert bench["source"] == entry["source_url"]
    differ = {k for k, v in entry["config"].items() if CFG.get(k) != v}
    assert differ == set(bench["reduced"]) == set(CFG["reduced"]) == {
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"}
    assert CFG["n_routed_experts_published"] \
        == entry["config"]["n_routed_experts"] == 256
    assert CFG["vocab_size_published"] == entry["config"]["vocab_size"]
    assert CFG["held_experts"] == list(range(16))
    assert CFG["vocab_size"] * 8 == CFG["vocab_size_published"]
    # every width as published
    for key, value in (("hidden_size", 6144), ("num_attention_heads", 64),
                       ("qk_nope_head_dim", 192), ("qk_rope_head_dim", 64),
                       ("v_head_dim", 256), ("q_lora_rank", 2048),
                       ("kv_lora_rank", 512), ("index_n_heads", 32),
                       ("index_head_dim", 128), ("index_topk", 2048),
                       ("num_experts_per_tok", 8),
                       ("intermediate_size", 12288),
                       ("moe_intermediate_size", 2048)):
        assert CFG[key] == value
    for key in ("assumed", "program_departures", "deployment", "arithmetic"):
        assert CFG[key]
