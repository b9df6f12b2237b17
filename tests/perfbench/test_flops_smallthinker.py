"""``harness/flops_smallthinker.py`` against hand counts at SmallThinker's
published widths, and the configuration file against the catalog's keys."""
import json
import os

import pytest

from perfbench.harness import flops_smallthinker as F
from perfbench.harness import spec

CFG = spec.load_json(spec.ROOT, "perfbench/configs/smallthinker_21b.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PEAKS = spec.load_json(spec.BENCH_DIR, "peaks.json")["devices"]["TPU v5 lite"]


def test_layer_parameters_are_the_issues_arithmetic():
    # q and o 2560 x 3584, k and v 2560 x 512
    assert F.attention_params(CFG) == 2 * 2560 * 3584 + 2 * 2560 * 512 \
        == 20971520
    assert F.expert_params(CFG) == 3 * 2560 * 768 == 5898240
    layer = 20971520 + 2560 * 64 + 64 * 5898240
    assert F.layer_params(CFG) == layer == 398622720      # 797 MB in bf16
    total = 8 * layer + 2 * 151936 * 2560
    assert F.param_count(CFG) == total == 3966894080      # 7.93 GB in bf16


def test_a_window_layer_sees_the_window_at_most():
    assert F.keys_seen(CFG, 0, 9000) == 9000
    assert F.keys_seen(CFG, 1, 9000) == 4096
    assert F.keys_seen(CFG, 1, 300) == 300


def test_forward_flops_count_six_experts_and_the_windows_cap():
    dense = 2 * (20971520 + 2560 * 64 + 6 * 5898240)      # 113 MFLOP
    assert dense == 113049600
    near = 8 * (dense + 4 * 28 * 128 * 1000)
    assert F.position_flops(CFG, 1000) == near
    far = 2 * (dense + 4 * 28 * 128 * 12000) \
        + 6 * (dense + 4 * 28 * 128 * 4096)
    assert F.position_flops(CFG, 12000) == far
    assert F.forward_flops(CFG, 10, 12000, False) == 10 * far
    assert F.forward_flops(CFG, 10, 12000, True) == \
        10 * (far + 2 * 2560 * 151936)
    # all 64 experts would be 8 x 2 x 58 x 5.9 M more: not counted
    assert far < 8 * 2 * F.layer_params(CFG) / 3


def test_the_experts_least_time_is_bytes_in_decode_and_flops_in_a_chunk():
    # 60 touched experts' three bf16 matrices against 168 assignments
    nbytes = 60 * 5898240 * 2
    flops = 168 * 2 * 5898240
    got = F.experts_least_seconds(CFG, 60, 168, PEAKS)
    assert got == pytest.approx(nbytes / 819e9) and \
        nbytes / 819e9 > flops / 197e12
    # a chunk of 2,048 positions, 12,288 assignments, is still bound by the
    # 64 experts' bytes (0.92 ms against 0.74); twice as many are not
    got = F.experts_least_seconds(CFG, 64, 12288, PEAKS)
    assert got == pytest.approx(64 * 5898240 * 2 / 819e9)
    got = F.experts_least_seconds(CFG, 64, 24576, PEAKS)
    assert got == pytest.approx(24576 * 2 * 5898240 / 197e12)


def test_least_bytes_of_a_decode_steps_attention():
    row = 2 * 4 * 128 * 2                                  # K and V, bf16
    assert F.kv_read_bytes(CFG, 1000) == 8 * 1000 * row
    assert F.kv_read_bytes(CFG, 12000) == (2 * 12000 + 6 * 4096) * row \
        == 99483648


def test_the_file_holds_the_catalogs_keys_but_for_what_it_reduced():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    rows = [json.loads(l) for l in open(CATALOG) if l.strip()]
    entry = next(r for r in rows
                 if r["name"] == "SmallThinker-21BA3B-Instruct")
    bench = next(c for c in spec.benchmark()["configs"]
                 if c["name"] == "smallthinker_21b")
    assert bench["source"] == entry["source_url"]
    differ = {k for k, v in entry["config"].items() if CFG.get(k) != v}
    assert differ == set(bench["reduced"]) == set(CFG["reduced"])
    for k in ("sliding_window_layout", "rope_layout"):
        assert CFG[k] == entry["config"][k][:8]
