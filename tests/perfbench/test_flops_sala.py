"""``harness/flops_sala.py`` against hand counts at MiniCPM-SALA's published
widths, and the configuration file against the catalog's keys."""
import json
import os

import pytest

from perfbench.harness import flops_sala as F
from perfbench.harness import spec

CFG = spec.load_json(spec.ROOT, "perfbench/configs/minicpm_sala.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_layer_parameters_are_the_issues_arithmetic():
    # sparse: q 4096x4096, k and v 4096x256, o and gate 4096x4096, FFN
    # 3 x 4096 x 16384
    sparse = 3 * 4096 * 4096 + 2 * 4096 * 256 + 3 * 4096 * 16384
    assert F.layer_params(CFG, F.SPARSE) == sparse == 253755392
    linear = 5 * 4096 * 4096 + 3 * 4096 * 16384
    assert F.layer_params(CFG, F.LINEAR) == linear == 285212672
    assert F.count(CFG, F.SPARSE) == 4 and F.count(CFG, F.LINEAR) == 12
    total = 4 * sparse + 12 * linear + 2 * 73448 * 4096
    assert F.param_count(CFG) == total == 5039259648   # 10.08 GB in bf16


def test_positions_read_follow_the_selection():
    assert F.positions_read(CFG, 5000) == 5000
    assert F.positions_read(CFG, 8192) == 8192
    # 1 initial + 33 local + 64 chosen blocks of 64
    assert F.positions_read(CFG, 8193) == 98 * 64 == 6272
    assert F.positions_read(CFG, 25000) == 6272


def test_mixer_and_forward_flops():
    assert F.mixer_flops(CFG, F.LINEAR, 12345) == 4 * 32 * 128 * 128
    assert F.mixer_flops(CFG, F.SPARSE, 4000) == 4 * 32 * 128 * 4000
    far = 4 * 32 * 128 * 6272 + 2 * 32 * 128 * (12000 // 16)
    assert F.mixer_flops(CFG, F.SPARSE, 12000) == far
    each = 4 * (2 * 253755392 + far) + 12 * (2 * 285212672 + 2097152)
    assert F.forward_flops(CFG, 1, 12000, False) == each
    assert F.forward_flops(CFG, 10, 12000, True) == \
        10 * (each + 2 * 4096 * 73448)
    # the products are the bulk: the mixers are about 5 % at 12k
    assert 0.03 < 1 - 2 * (F.param_count(CFG) - 2 * 73448 * 4096) / each \
        < 0.08


def test_least_bytes_of_a_decode_step():
    assert F.linear_state_bytes(CFG) == 2 * 4 * 32 * 128 * 128 == 4194304
    near = 2 * 5000 * 2 * 128 * 2
    assert F.sparse_read_bytes(CFG, 5000) == near
    far = 2 * 6272 * 2 * 128 * 2 + (12000 // 16) * 2 * 128 * 2
    assert F.sparse_read_bytes(CFG, 12000) == far == 6806528


def test_the_file_holds_the_catalogs_keys_but_for_what_it_reduced():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    rows = [json.loads(l) for l in open(CATALOG) if l.strip()]
    (entry,) = [r for r in rows if r["name"] == "MiniCPM-SALA"]
    reduced = set(CFG["reduced"])
    assert reduced == {"num_hidden_layers", "mixer_types"}
    for key, value in entry["config"].items():
        if key not in reduced:
            assert CFG[key] == value, key
    assert CFG["mixer_types"] == entry["config"]["mixer_types"][9:25]
    assert CFG["num_hidden_layers"] == len(CFG["mixer_types"]) == 16
    row = next(c for c in spec.benchmark()["configs"]
               if c["name"] == "minicpm_sala")
    assert row["source"] == entry["source_url"]
    assert set(row["reduced"]) == reduced


def test_the_serving_block_fills_what_the_arithmetic_says():
    serving = CFG["serving"]
    assert serving["kv_pages"] == serving["slots"] * (
        CFG["n_positions"] // serving["kv_page_len"]) + 1 == 4705
    assert CFG["n_positions"] == 24576 + serving["max_new_tokens"] == 25088
    assert serving["kv_page_len"] == CFG["sparse_attention"]["block_size"]
    mix = spec.load_like("traffic", "longdoc_closed")
    lengths = [q[1] for q in mix["prompt_len"]["quantiles"]]
    assert min(lengths) > CFG["sparse_attention"]["dense_len"]
    assert max(lengths) + mix["output_len"]["quantiles"][-1][1] \
        <= CFG["n_positions"]
