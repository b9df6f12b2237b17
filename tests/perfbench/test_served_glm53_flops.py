"""``harness/flops_glm53.py`` against hand counts at GLM-5.3-Flash's published
widths, and the configuration file against the published config's keys."""
import os

import pytest

from perfbench.harness import flops_glm53 as F
from perfbench.harness import spec

CFG = spec.load_json(spec.ROOT, "perfbench/configs/glm_5_3_flash.json")
CATALOG = os.path.join(os.path.dirname(__file__), "data",
                       "glm_5_3_flash.catalog.json")


def test_layer_parameters_are_the_hand_counts():
    # q, k, v 100.66 M, o 33.55, b 0.26, decay and gate pairs 3.15
    assert F.kda_params(CFG) == 4 * 4096 * 8192 + 4096 * 64 \
        + 2 * (4096 * 128 + 128 * 8192) == 137625600
    # q_a 6.29 M, q_b 25.17, kv_a 2.10, kv_b 16.78, o 67.11: 117.4 M
    assert F.attention_params(CFG) == 4096 * 1536 + 1536 * 64 * 256 \
        + 4096 * 512 + 512 * 64 * 512 + 64 * 256 * 4096 == 117440512
    assert F.indexer_params(CFG) == 1536 * 32 * 128 + 4096 * (128 + 32) \
        == 6946816
    mhc = 2 * 4 * 4096 * 4 * 6
    assert mhc == 786432
    dense = 137625600 + 3 * 4096 * 12288 + mhc
    assert F.layer_params(CFG, "linear_attention", "dense") == dense \
        == 289406976
    moe = 4096 * 288 + 25165824 + 36 * 25165824 + mhc
    assert F.layer_params(CFG, "deepseek_sparse_attention", "sparse") \
        == 117440512 + 6946816 + moe == 1057488896
    assert F.layer_params(CFG, "linear_attention", "sparse") \
        == 137625600 + moe == 1070727168
    total = dense + 1057488896 + 3 * 1070727168 + 2 * 19360 * 4096
    assert F.param_count(CFG) == total == 4717674496       # 9.44 GB
    assert (F.kda_layers(CFG), F.dsa_layers(CFG), F.dense_layers(CFG),
            F.expert_layers(CFG)) == (4, 1, 1, 4)


def test_forward_flops_count_every_kind_of_layer():
    # the recurrence: three state-sized products with a vector a head, and
    # the convolution of 4 taps over q, k and v
    rec = 6 * 64 * 128 * 128 + 2 * 4 * 3 * 8192
    assert F.kda_position_flops(CFG) == rec
    assert F.mhc_flops(CFG) == 2 * 2 * 4 * 4096 * 24
    experts = 2 * (4096 * 288 + 25165824 + 1.0 * 25165824)
    # beyond 2,048 positions the indexer scores every whole block of 4,
    # attention reads the 2,048 positions selected
    dsa = 2 * (117440512 + 6946816) + 2 * 32 * 128 * (10000 // 4) \
        + 2 * 64 * 512 * 2048
    kda = 2 * 137625600 + rec
    want = dsa + 4 * kda + 5 * F.mhc_flops(CFG) + 2 * 3 * 4096 * 12288 \
        + 4 * experts
    assert F.position_flops(CFG, 10000) == want
    assert F.forward_flops(CFG, 3, 10000, True) == pytest.approx(
        3 * (want + 2 * 4096 * 19360))


def test_the_configuration_keeps_the_catalogs_numbers():
    """Every number of the catalog row's config is in the file under its
    key, equal unless the key is listed in ``reduced``; nested groups are
    copied whole, the one changed group is listed by its top-level key."""
    row = spec.load_json(os.path.dirname(CATALOG), os.path.basename(CATALOG))
    assert CFG["source"].startswith(row["source_url"])
    for key, value in row["config"].items():
        assert key in CFG, key
        if key not in CFG["reduced"]:
            assert CFG[key] == value, key
    lin = row["config"]["linear_attn_config"]
    for key in ("num_heads", "head_dim", "short_conv_kernel_size",
                "gate_lower_bound"):
        assert CFG["linear_attn_config"][key] == lin[key]
