"""The benchmark's own arithmetic: the traffic generator, rates and tails
from a request log, the trace reduction, the FLOP and byte counts, and the
shape of ``BENCHMARK.json``."""
import collections
import json
import os
import re

import numpy as np
import pytest

from perfbench.harness import flops, spec, stats, tracing, traffic

BENCH = spec.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


# -- traffic -----------------------------------------------------------------

def _mix(loop):
    name = {"open": "chat_open", "closed": "doc_closed"}[loop]
    return spec.load_json(spec.BENCH_DIR, "traffic", name + ".json")


def _shape(plan):
    return [(len(r["prompt"]), r["max_new"]) for r in plan]


@pytest.mark.parametrize("loop", ["open", "closed"])
def test_every_seed_offers_the_same_cycle_from_another_start(loop):
    mix = _mix(loop)
    grid = int(mix["grid"])
    seeds = (1, 2, 2 ** 32 + 5, 11)
    plans = {s: traffic.requests(mix, 1000, s, 3 * grid) for s in seeds}
    starts = {s: traffic.start_of(mix, s) for s in seeds}
    assert len(set(starts.values())) > 1
    base = _shape(plans[1])
    for s in seeds:
        shape = _shape(plans[s])
        # the same multiset in every cycle, and the same neighbours: one
        # seed's plan is another's, moved along the cycle
        assert sorted(shape[:grid]) == sorted(base[:grid])
        assert shape[:grid] == shape[grid:2 * grid] == shape[2 * grid:]
        move = (starts[s] - starts[1]) % grid
        assert shape[:grid] == (base + base)[move:move + grid]
    assert plans[1][0]["prompt"] != plans[2][0]["prompt"] or \
        len(plans[1][0]["prompt"]) != len(plans[2][0]["prompt"])
    assert traffic.requests(mix, 1000, 1, 3 * grid) == plans[1]
    # drawn in two parts, a plan is the same plan
    parts = traffic.requests(mix, 1000, 1, grid) + \
        traffic.requests(mix, 1000, 1, 2 * grid, first=grid)
    assert _shape(parts) == base
    assert [r["id"] for r in parts] == list(range(3 * grid))
    if loop == "open":
        assert [r["due"] for r in parts] == pytest.approx(
            [r["due"] for r in plans[1]])


def test_open_loop_cycles_last_exactly_grid_over_rate():
    mix = _mix("open")
    grid, rate = int(mix["grid"]), float(mix["rate_per_s"])
    gaps = {}
    for seed in (3, 4):
        plan = traffic.requests(mix, 1000, seed, 2 * grid)
        due = [r["due"] for r in plan]
        assert due[grid - 1] == pytest.approx(grid / rate)
        assert due[-1] == pytest.approx(2 * grid / rate)
        gaps[seed] = np.diff([0.0] + due[:grid])
        assert min(gaps[seed]) > 0
    assert sorted(gaps[3]) == pytest.approx(sorted(gaps[4]))
    want = np.sort(-np.log1p(-(np.arange(grid) + 0.5) / grid))
    assert np.sort(gaps[3]) / gaps[3].sum() == pytest.approx(
        want / want.sum())


def test_quantile_grid_follows_the_file():
    mix = _mix("closed")
    lens = traffic.quantile_grid(mix["prompt_len"]["quantiles"], 1000)
    assert lens.min() >= 192 and lens.max() <= 512
    assert abs(int(np.median(lens)) - 320) <= 1
    with pytest.raises(ValueError):
        traffic.quantile_grid([[0.1, 1], [1, 2]], 4)


def test_classification_rows_share_lengths_across_seeds():
    mix = spec.load_json(spec.BENCH_DIR, "traffic", "glue_s128.1chip.json")
    rows = [traffic.classification_rows(mix, 30522, s, 256) for s in (7, 8)]
    real = [np.sort((t != 0).sum(axis=1)) for t, _ in rows]
    assert (real[0] == real[1]).all()
    assert real[0].min() >= 8 and 120 <= real[0].max() <= 128
    assert int(np.median(real[0])) in range(38, 43)
    assert not (rows[0][0] == rows[1][0]).all()
    tokens, labels = rows[0]
    assert ((tokens[:, 1] % 2) == labels).all()
    assert tokens.min() == 0 and tokens.max() < 30522
    # every batch, and every chip's shard of it, holds the same classes
    assert {int(labels[i:i + 32].sum()) for i in range(0, 256, 32)} == {8}
    assert len({r.tobytes() for r in tokens}) == len(tokens)


# -- rates and tails -----------------------------------------------------------

def _log(stall=0.0):
    """40 requests, one due every 0.25 s from t = 10, first token after
    0.2 s and then 9 more, 0.1 s apart. With a ``stall`` the server stops
    for that long at t = 11, 13, 15, 17 and 19: every token due after such a
    moment comes that much later."""
    log = []
    for i in range(40):
        due = 10 + 0.25 * i
        times = [due + 0.2 + 0.1 * k for k in range(10)]
        times = [t + stall * sum(t >= at for at in (11, 13, 15, 17, 19))
                 for t in times]
        log.append({"id": i, "due": due, "sent": due + 0.001, "max_new": 10,
                    "token_times": times, "done": True})
    return log


def _metrics(log, t0=10.0, t1=20.0):
    return {"ttft_p95_ms": stats.percentile(stats.ttft_ms(log, t0, t1, 9e4),
                                            95),
            "tpot_p95_ms": stats.percentile(stats.token_gaps_ms(log, t0, t1),
                                            95),
            "out_tokens_per_s": stats.tokens_in(log, t0, t1) / (t1 - t0)}


def test_rates_and_tails_on_a_hand_made_log():
    m = _metrics(_log())
    assert m["ttft_p95_ms"] == pytest.approx(200.0)
    assert m["tpot_p95_ms"] == pytest.approx(100.0)
    # 400 tokens, of which those that arrive from t = 20 on are not counted
    late = sum(t >= 20 for r in _log() for t in r["token_times"])
    assert 0 < late < 30
    assert m["out_tokens_per_s"] == pytest.approx((400 - late) / 10.0)
    assert stats.percentile([], 95) is None
    assert stats.percentile(list(range(1, 101)), 95) == 95
    assert stats.percentile([5.0], 95) == 5.0


def test_a_stall_in_the_window_moves_every_end_to_end_metric():
    calm, stalled = _metrics(_log()), _metrics(_log(stall=1.0))
    assert stalled["ttft_p95_ms"] > calm["ttft_p95_ms"] + 2000
    assert stalled["tpot_p95_ms"] > calm["tpot_p95_ms"] + 900
    assert stalled["out_tokens_per_s"] < calm["out_tokens_per_s"]


def test_a_failed_request_counts_as_the_worst():
    log = _log()
    log[3]["done"] = False
    log[5]["token_times"] = log[5]["token_times"][:4]
    assert sorted(stats.ttft_ms(log, 10, 20, 9e4))[-2:] == [9e4, 9e4]
    assert sum(stats.failed(r) for r in log) == 2
    assert stats.late_ms(log, 10, 20) == pytest.approx([1.0] * 40)


# -- the trace reduction --------------------------------------------------------

def _planes():
    """A recorded trace in small: one device, two runs of a program of two
    operations each, a second program, and the two marks."""
    ops = [("fusion.1", 1.0, 0.2), ("copy.7", 1.2, 0.1),     # run 1
           ("fusion.1", 2.0, 0.2), ("copy.9", 2.2, 0.1),     # run 2
           ("custom-call.3", 3.0, 0.5),                      # other program
           ("fusion.1", 9.0, 0.2)]                           # after the window
    modules = [("jit_step(1)", 1.0, 0.3), ("jit_step(1)", 2.0, 0.3),
               ("jit_prefill(2)", 3.0, 0.5), ("jit_step(1)", 9.0, 0.2)]
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "sync": [0.5, 4.5]}


def test_trace_reduction_busy_idle_and_gap_attribution():
    # host spans on perf_counter, which runs 100 s ahead of the trace
    spans = [("serve.fetch", 101.3, 0.7),        # covers the gap 1.3 .. 2.0
             ("outer", 100.4, 4.2),              # covers everything
             ("queue.put", 102.35, 0.6)]         # covers the gap 2.3 .. 3.0
    reduced = tracing.reduce(_planes(), [100.5, 104.5], spans)
    assert reduced["window_s"] == pytest.approx(4.0)
    assert reduced["busy_s"] == pytest.approx(0.3 + 0.3 + 0.5)
    ops = dict(map(tuple, reduced["breakdown"]["device_ops"]))
    assert ops == pytest.approx({"fusion": 0.4, "copy": 0.2,
                                 "custom-call": 0.5})
    gaps = dict(map(tuple, reduced["breakdown"]["idle_gaps"]))
    assert gaps == pytest.approx({"serve.fetch": 0.7, "queue.put": 0.7,
                                  "outer": 0.5 + 1.0})
    assert sum(gaps.values()) == pytest.approx(4.0 - 1.1)
    assert tracing.module_runs(reduced, "step") == [(1.0, 0.3), (2.0, 0.3)]
    assert tracing.op_seconds(reduced, "^copy") == (2, pytest.approx(0.2))


def test_trace_reduction_without_marks_or_devices_reads_nothing():
    planes = _planes()
    assert tracing.reduce(dict(planes, sync=[1.0]), [0, 1]) is None
    assert tracing.reduce(dict(planes, devices={}), [0, 1]) is None
    unnamed = tracing.reduce(_planes(), [100.5, 104.5], [])
    assert [g[0] for g in unnamed["breakdown"]["idle_gaps"]] == ["host:no_span"]


def test_union_and_gaps():
    merged = tracing.union([(3, 4), (1, 2), (1.5, 2.5), (2.5, 2.6)])
    assert merged == [(1, 2.6), (3, 4)]
    assert tracing.gaps(merged, 0, 5) == [(0, 1), (2.6, 3), (4, 5)]
    assert tracing.clip([(0, 2), (3, 9)], 1, 4) == [(1, 2), (3, 4)]


# -- FLOPs and bytes against hand counts ------------------------------------------

BERT = spec.load_json(spec.ROOT, "perfbench/configs/bert_base.json")
GPT2 = spec.load_json(spec.ROOT, "perfbench/configs/gpt2_small.json")
PEAKS = spec.load_json(spec.BENCH_DIR, "peaks.json")["devices"]["TPU v5 lite"]


def test_bert_base_train_flops_per_token():
    # a block: 4 x 768^2 + 2 x 768 x 3072 = 7,077,888 weights; 12 blocks
    assert flops.encoder_block_params(768, 3072) == 7077888
    # attention at 128 keys: 2 products x 2 x 128 x 768 = 393,216 forward
    assert flops.attention_flops(1, 128, 768) == 393216
    want = 12 * (6 * 7077888 + 3 * 393216)
    assert flops.bert_train_flops_per_token(BERT, 128) == want == 523763712


def test_gpt2_small_forward_flops_and_bytes():
    # one decoded position over 300 keys, with the head
    want = 12 * (2 * 7077888 + 2 * 2 * 300 * 768) + 2 * 768 * 50257
    assert flops.lm_forward_flops(GPT2, 1, 300, True) == want
    assert flops.lm_forward_flops(GPT2, 10, 300, False) == \
        10 * 12 * (2 * 7077888 + 2 * 2 * 300 * 768)
    # 124,439,808 parameters (the published count), 4 bytes each
    assert flops.lm_param_bytes(GPT2) == 4 * 124439808
    kv = 2 * 12 * 1000 * 768 * 4
    assert flops.decode_step_bytes(GPT2, 1000) == 4 * 124439808 + kv


def test_short_attention_cost_and_roofline():
    fwd = flops.short_attention_cost(128, 12, 128, 64, 2, backward=False)
    assert fwd == (2 * 2 * 128 * 12 * 128 * 128 * 64,
                   4 * 128 * 12 * 128 * 64 * 2)
    bwd = flops.short_attention_cost(128, 12, 128, 64, 2, backward=True)
    assert bwd[0] == 5 * fwd[0] // 2 and bwd[1] == 2 * fwd[1]
    seconds, bound = flops.roofline_seconds(*fwd, PEAKS)
    assert bound == "memory"
    assert seconds == pytest.approx(fwd[1] / 819e9)
    assert flops.roofline_seconds(1e15, 1.0, PEAKS)[1] == "compute"


# -- BENCHMARK.json -----------------------------------------------------------------

def test_benchmark_names_units_and_files():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = collections.Counter()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for row in BENCH[group]:
            assert NAME.match(row["name"]), row["name"]
            names[(group in ("end_to_end", "per_layer"), row["name"])] += 1
    assert max(names.values()) == 1
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.1
    for c in BENCH["configs"]:
        assert os.path.isfile(os.path.join(spec.ROOT, c["file"]))
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
        assert len(c["why"]) <= 200 and len(c["source"]) <= 200
        assert any(w["config"] == c["name"] for w in BENCH["workloads"])
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_every_cell_reports_setup_another_metric_and_a_layer():
    for w in BENCH["workloads"]:
        assert len(w["why"]) <= 200 and NAME.match(w["traffic"])
        cell = spec.Cell(w["name"])
        e2e = [m["name"] for m in cell.end_to_end()]
        assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
        assert cell.per_layer(), w["name"]
        assert os.path.isfile(os.path.join(
            spec.BENCH_DIR, "limits", w["name"] + ".json"))
        assert cell.kind in ("train", "generate")


def test_every_per_layer_metric_has_a_reader_and_cells_that_report_what_it_moves():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    cells = [w["name"] for w in BENCH["workloads"]]
    layers = collections.defaultdict(set)
    for m in BENCH["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert callable(spec.metric_reader(m["name"]))
        moved = e2e[m["moves"]]
        for cell in m["workloads"]:
            assert cell in cells
            assert cell in moved.get("workloads", cells), (m["name"], cell)
        layers[m["layer"].split(" (")[0]].add(m["layer"])
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
    assert all(len(v) == 1 for v in layers.values())
    assert any("mfu" in m["name"] for m in BENCH["per_layer"]
               if m["moves"] == "train_tokens_per_s")


def test_tails_decide_in_the_open_loop_only():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["tpot_p95_ms"]["workloads"] == ["gpt2_small.chat_open"]
    per_layer = {m["name"]: m for m in BENCH["per_layer"]}
    for tail in ("ttft_p95_ms.doc", "tpot_p95_ms.doc"):
        assert per_layer[tail]["workloads"] == ["gpt2_small.doc_closed"]
    # time to first token could not be held steady over 160 requests
    # (PERF.md, PR 24): it is recorded a layer down, under a name of its own
    assert "ttft_p95_ms" not in e2e
    assert per_layer["ttft_p95_ms.chat"]["workloads"] == \
        ["gpt2_small.chat_open"]


def test_peaks_table_is_keyed_by_exact_device_kind():
    table = spec.load_json(spec.BENCH_DIR, "peaks.json")
    assert table["source"]
    assert table["devices"]["TPU v5 lite"]["bf16_flops"] == 197e12
    assert table["devices"]["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9


# -- readers on the small trace ----------------------------------------------------

@pytest.mark.parametrize("name,short", [
    ("%fusion.6 = f32[30522,768]{1,0:T(8,128)} fusion(s32[16384]{0} %x), "
     "kind=kCustom", "fusion_f32_30522_768"),
    ("%transpose_jvp___.22 = (bf16[1536,128,64]{2,1,0}, bf16[1536,128,64]"
     "{2,1,0}) custom-call(bf16[1536,128,64]{2,1,0} %b), custom_call_target="
     "\"tpu_custom_call\"", "transpose_jvp____bf16_1536_128_64_custom-call"),
    ("%slice-start.12 = ((f32[30522,768]{1,0}), f32[7632,768]{1,0}) "
     "slice-start(f32[1]{0} %x)", "slice-start_f32_30522_768"),
    ("%copy.5 = f32[3073,12,16,64]{3,2,1,0} copy(f32[3073,12,16,64]{3,2,1,0} "
     "%p)", "copy_f32_3073_12_16_64"),
    ("dot_general.1", "dot_general"),
])
def test_operations_get_short_names(name, short):
    assert tracing.label(name) == short


def _ctx(**more):
    class Capture:
        sync = [100.5, 104.5]
    spans = tracing.HostSpans()
    for i in range(4):
        spans.add("train_step", 101.0 + i, 0.01)
        spans.add("feed.next", 101.25 + i * 0.5, 0.002)
    spans.add("feed.next", 90.0, 5.0)  # before the traced stretch
    ctx = {"trace": tracing.reduce(_planes(), [100.5, 104.5], []),
           "capture": Capture(), "spans": spans, "peaks": PEAKS,
           "device": {"memory_peak_bytes": 4 * 10 ** 9}}
    ctx.update(more)
    return ctx


def test_shared_readers_on_the_small_trace():
    from perfbench.harness import readers
    ctx = _ctx()
    assert readers.device_idle_pct(ctx) == pytest.approx(100 * 2.9 / 4.0)
    assert readers.hbm_peak_gb(ctx) == pytest.approx(4.0)
    assert readers.spans_ms_per(ctx, ("feed.next",), "train_step") == \
        pytest.approx(2.0)
    assert readers.module_ms(ctx, "step") == pytest.approx(300.0)
    assert readers.module_ms(ctx, "nothing") is None
    assert readers.module_period_ms(ctx, "step") is None  # two runs only
    assert readers.module_share_pct(ctx, "prefill") == pytest.approx(
        100 * 0.5 / 1.1)
    untraced = dict(ctx, trace=None, capture=None)
    assert readers.device_idle_pct(untraced) is None
    assert readers.spans_ms_per(untraced, ("feed.next",), "train_step") is None
    assert readers.hbm_peak_gb(dict(ctx, device={"memory_peak_bytes": 0})) \
        is None


def test_a_roofline_reader_returns_nothing_rather_than_nought():
    read = spec.metric_reader("attn_short_roofline.train")
    cell = spec.Cell("bert_base.glue_s128.1chip")
    ctx = _ctx(cell=cell, batch=128, seq=128, chips=1)
    assert read(ctx) is None  # the small trace holds no kernel
    kernel = ("%{}jvp___.{} = bf16[1536,128,64]{{2,1,0}} custom-call(bf16[1] "
              "%x), custom_call_target=\"tpu_custom_call\"")
    planes = _planes()
    fwd = flops.roofline_seconds(*flops.short_attention_cost(
        128, 12, 128, 64, 2, backward=False), PEAKS)[0]
    bwd = flops.roofline_seconds(*flops.short_attention_cost(
        128, 12, 128, 64, 2, backward=True), PEAKS)[0]
    planes["devices"]["/device:TPU:0"]["ops"] += [
        (kernel.format("", 1), 3.6, 2 * fwd),
        (kernel.format("transpose_", 2), 3.8, 4 * bwd)]
    ctx["trace"] = tracing.reduce(planes, [100.5, 104.5], [])
    want = 100 * (fwd + bwd) / (2 * fwd + 4 * bwd)
    assert read(ctx) == pytest.approx(want)
    assert 25 < want < 50


def test_spread_is_the_interquartile_distance_over_the_median():
    import importlib.util
    path = os.path.join(spec.BENCH_DIR, "tools", "spread.py")
    module_spec = importlib.util.spec_from_file_location("pb_spread", path)
    module = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(module)
    # statistics.quantiles([1..6], n=4) = [1.75, 3.5, 5.25]
    assert module.spread([1, 2, 3, 4, 5, 6]) == pytest.approx(3.5 / 3.5)
    assert module.spread([100, 100, 100, 100, 100, 101]) == \
        pytest.approx(0.25 / 100)
