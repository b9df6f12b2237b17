"""The readers that ``glm_5.longctx_closed`` adds, on a planted trace,
hand-made spans and snapshots, as ``test_readers_smallthinker.py`` does for
its cell: the share and roofline readers find the new scopes inside the
decode program, the counter readers take the window's share, and every one
reads nothing (and does not raise) where the program has no such scope,
span or counter."""
import pytest

from perfbench.harness import (flops_glm5, readers_glm5, readers_sala, scopes,
                               spec, tracing)

CELL = "glm_5.longctx_closed"
PEAKS = spec.load_json(spec.BENCH_DIR, "peaks.json")["devices"]["TPU v5 lite"]


class _Capture:
    sync = [100.0, 110.0]

    def path(self):
        return "unused"


def _trace():
    """Two decode steps (1.0-1.02, 2.0-2.02) and one chunk (3.0-3.2) on the
    trace's clock; the window is 0 .. 10 there, 100 .. 110 on the host's."""
    modules = [("jit__step_paged(1)", 1.0, 0.02),
               ("jit__step_paged(1)", 2.0, 0.02),
               ("jit__prefill_chunk(2)", 3.0, 0.2)]
    busy = [(1.0, 1.02), (2.0, 2.02), (3.0, 3.2)]
    return {"t0": 0.0, "t1": 10.0, "window_s": 10.0, "busy_s": 0.24,
            "devices": {"/device:TPU:0": {"ops": [], "busy": busy,
                                          "modules": modules}}}


def _ops():
    def op(a, b, *names):
        return ("%fusion.1 = f32[4] fusion(...)", a, b, names, False)

    def ragged(a, b):   # as the chip's trace has them: no op_name, no scope
        return ("%ragged-dot-none.3 = f32[96,2048]{1,0} custom-call(...)",
                a, b, (), False)
    step = [("mla_project", 3), ("dsa_index", 2), ("dsa_select", 3),
            ("mla_attend", 4), ("moe_route", 1), ("moe_experts", 2)]
    out = []
    for start in (1.0, 2.0):
        at = start
        for name, ms in step:
            out.append(op(at, at + ms / 1e3, name))
            at += ms / 1e3
        out += [ragged(at, at + 0.003), op(at + 0.003, at + 0.004,
                                           "moe_shared")]
    return out + [
        op(3.000, 3.020, "mla_project"), op(3.020, 3.040, "dsa_index"),
        op(3.040, 3.050, "dsa_select"), op(3.050, 3.070, "mla_attend"),
        ("%body.16 = (f32[64,2048,256], f32[64,2048,1]) custom-call(...)",
         3.070, 3.150, ("mla_attend",), False),
        op(3.150, 3.160, "moe_experts"), ragged(3.160, 3.180),
        op(3.180, 3.190, "moe_shared"), op(3.190, 3.195, "ffn"),
        ("%copy.7 = bf16[64] copy(...)", 3.195, 3.200, (), False)]


def _log():
    """Three streams decoding through both steps (contexts 12,000 / 900 /
    30,000 and a few tokens), one that ended before, one whose first token
    comes later."""
    def stream(prompt, first, last):
        return {"prompt": [1] * prompt, "done": True,
                "token_times": [first, first + 0.5, last]}
    return [stream(12000, 100.5, 108.0), stream(900, 100.2, 109.0),
            stream(30000, 99.0, 107.0), stream(9000, 96.0, 100.9),
            stream(9000, 104.0, 109.0)]


def _snap(steps, touched, load, assignments, read, scored, occupied=9,
          chunks=0, fed=0):
    return {"slots_occupied": occupied, "prefills_pending": 1,
            "decode_steps_total": steps, "prefill_chunks_total": chunks,
            "prompt_tokens_total": fed,
            "moe_experts_touched": {"mean": touched, "window": steps},
            "moe_expert_load": {"mean": load, "window": steps},
            "moe_assignments_total": assignments,
            "sparse_positions_read": {"mean": read, "window": steps},
            "dsa_positions_scored": {"mean": scored, "window": steps},
            "kv_pages_in_use": {"full": 3000, "window": None}}


KEPT = [(90.0, _snap(100, 3.0, 5.0, 1500, 1500.0, 5000.0)),     # the ramp
        (96.0, _snap(200, 4.0, 4.0, 3000, 1800.0, 8000.0)),
        # two inside the traced stretch (100 .. 110): 2 steps, 3 held
        # experts touched a layer a step, 24 assignments; 1 chunk of 1,500
        (100.5, _snap(400, 4.0, 4.0, 6000, 1900.0, 9000.0, chunks=7,
                      fed=9000)),
        (109.5, _snap(402, (4.0 * 400 + 2 * 3.0) / 402, 4.0, 6024, 1900.0,
                      9000.0, chunks=8, fed=10500)),
        (120.0, _snap(1000, 4.4, 3.8, 20000, 2000.0, 11000.0)),
        (134.0, _snap(2200, 4.3, 3.6, 3000 + 2000 * 4 * 5, 2025.0,
                      12000.0)),
        (140.0, _snap(9000, 4.2, 3.5, 9e6, 2040.0, 12500.0))]


def _ctx(monkeypatch):
    monkeypatch.setattr(scopes, "device_ops", lambda *a: _ops())
    cell = spec.Cell(CELL)
    monkeypatch.setattr(cell.adapter(), "SNAPSHOTS", KEPT)
    return {"cell": cell, "peaks": PEAKS, "trace": _trace(),
            "capture": _Capture(), "t0": 95.0, "t1": 135.0, "log": _log()}


def test_shares_read_the_new_scopes(monkeypatch):
    ctx = _ctx(monkeypatch)
    busy = 2 * 0.019 + 0.2          # the operations' own time
    assert spec.metric_reader("mla_share_pct.longctx")(dict(ctx)) == \
        pytest.approx(100 * (2 * 0.007 + 0.120) / busy)
    assert spec.metric_reader("dsa_share_pct.longctx")(dict(ctx)) == \
        pytest.approx(100 * (2 * 0.005 + 0.030) / busy)
    # route, experts with the compiler's grouped products, the shared one
    assert spec.metric_reader("moe_share_pct.longctx")(dict(ctx)) == \
        pytest.approx(100 * (2 * 0.007 + 0.040) / busy)
    assert spec.metric_reader("unscoped_share_pct.longctx")(dict(ctx)) == \
        pytest.approx(100 * 0.005 / busy)
    assert spec.metric_reader("prefill_chunk_ms.longctx")(ctx) == \
        pytest.approx(200.0)
    assert spec.metric_reader("decode_step_ms.longctx")(ctx) == \
        pytest.approx(20.0)


def test_counter_readers_take_the_windows_share(monkeypatch):
    ctx = _ctx(monkeypatch)
    got = spec.metric_reader("moe_experts_touched_mean.longctx")(ctx)
    assert got == pytest.approx((4.3 * 2200 - 4.0 * 200) / 2000) == 4.33
    got = spec.metric_reader("moe_load_max_over_mean.longctx")(ctx)
    assert got == pytest.approx((3.6 * 2200 - 4.0 * 200) / 2000) == 3.56
    got = spec.metric_reader("dsa_positions_read_mean.longctx")(ctx)
    assert got == pytest.approx((2025.0 * 2200 - 1800.0 * 200) / 2000)
    got = spec.metric_reader("dsa_positions_scored_mean.longctx")(ctx)
    assert got == pytest.approx((12000.0 * 2200 - 8000.0 * 200) / 2000) \
        == 12400.0


def test_rooflines_count_the_decode_programs_time_and_the_live_streams(
        monkeypatch):
    ctx = _ctx(monkeypatch)
    cfg = ctx["cell"].config
    assert readers_sala.live_contexts(ctx, 101.0) == [12002, 902, 30002]
    # the indexer and the selection: 5 ms a step of the decode program; the
    # chunk's 30 ms under the same scopes are not decode's
    least = 5 * sum(flops_glm5.index_least_seconds(cfg, c, PEAKS)
                    for c in (12002, 902, 30002))
    got = spec.metric_reader("dsa_index_roofline.longctx")(dict(ctx))
    assert got == pytest.approx(100 * 2 * least / 0.010) and 0 < got < 100
    # the sparse read: 2,048 rows of the two long streams, 902 of the short
    least = 5 * sum(flops_glm5.attend_least_seconds(cfg, c, PEAKS)
                    for c in (12002, 902, 30002))
    assert least == pytest.approx(5 * (2 * 2048 + 902) * 1152 / 819e9)
    got = spec.metric_reader("mla_attend_roofline.longctx")(dict(ctx))
    assert got == pytest.approx(100 * 2 * least / 0.008) and 0 < got < 100
    # the experts: without the compiler's kernels the scope reads 2 ms a
    # step, with them 5; between the two snapshots inside the stretch 3
    # held experts touched a layer a step and 3 assignments: bound by the
    # experts' bytes
    assert readers_sala.seconds_in_decode(dict(ctx), ("moe_experts",)) == \
        pytest.approx(0.004)
    least = 4 * 3.0 * flops_glm5.expert_params(cfg) * 2 / 819e9
    got = spec.metric_reader("moe_experts_roofline.longctx")(dict(ctx))
    assert got == pytest.approx(100 * 2 * least / 0.010) and 0 < got < 100
    # the chunk's kernel: 80 ms of Mosaic calls under mla_attend; one chunk
    # of 1,500 rows counted as a prompt's first, four layers
    flops = 1500 * 1501 / 2 * 4 * 2 * 64 * 512
    got = spec.metric_reader("mla_chunk_attend_roofline.longctx")(ctx)
    assert got == pytest.approx(100 * flops / 197e12 / 0.080)
    assert 0 < got < 100


def test_serve_mfu_counts_the_held_experts_assignments(monkeypatch):
    ctx = _ctx(monkeypatch)
    cfg = ctx["cell"].config
    # between the snapshots at 96 and 134: 40,000 assignments over the
    # tokens that reached a caller in between, four expert layers
    tokens = sum(1 for r in _log() for t in r["token_times"]
                 if 96.0 <= t < 134.0)
    assert tokens == 15          # all of the five streams' three
    counted = 40000 / tokens / 4
    assert readers_glm5.counted_assignments(ctx) == pytest.approx(counted)
    total = 0
    for p in (12000, 900, 30000, 9000, 9000):  # all five ended in 95 .. 135
        total += flops_glm5.forward_flops(cfg, p - 1, p / 2, False)
        total += flops_glm5.forward_flops(cfg, 3, p + 1.5, True, counted)
    got = spec.metric_reader("serve_mfu_pct.longctx")(ctx)
    assert got == pytest.approx(100 * total / 40 / 197e12) and 0 < got < 100


@pytest.mark.parametrize("name", [
    "mla_share_pct", "dsa_share_pct", "moe_share_pct",
    "dsa_positions_scored_mean", "dsa_positions_read_mean",
    "moe_experts_touched_mean", "moe_load_max_over_mean",
    "dsa_index_roofline", "mla_attend_roofline", "moe_experts_roofline",
    "mla_chunk_attend_roofline", "decode_steps_per_chunk", "prefill_chunk_ms", "prefill_chunk_share_pct",
    "decode_step_ms", "unscoped_share_pct", "device_idle_pct",
    "serve_host_ms_per_step", "serve_post_ms_per_step",
    "steps_ahead_share_pct", "fetch_wait_ms_per_step",
    "compiles_in_window", "serve_mfu_pct"])
def test_a_program_without_the_scope_span_or_counter_reads_nothing(
        monkeypatch, name):
    """A program that lacks what this cell's readers read (an earlier
    commit beside this benchmark): no trace, no spans of the program,
    snapshots without the new counters. No reader raises."""
    cell = spec.Cell(CELL)
    monkeypatch.setattr(cell.adapter(), "SNAPSHOTS",
                        [(96.0, {"slots_occupied": 1}),
                         (120.0, {"slots_occupied": 2})])
    ctx = {"cell": cell, "peaks": PEAKS, "trace": None, "capture": None,
           "t0": 95.0, "t1": 135.0, "log": [],
           "spans": tracing.HostSpans(),
           "device": {"memory_peak_bytes": 0}}
    assert spec.metric_reader(name + ".longctx")(ctx) is None


def test_every_metric_of_the_cell_has_a_reader():
    cell = spec.Cell(CELL)
    for m in cell.per_layer():
        assert callable(spec.metric_reader(m["name"]))
        assert m["moves"] == "out_tokens_per_s" \
            and m["workloads"] == [CELL]
    assert len(cell.per_layer()) == 27
    assert [m["name"] for m in cell.end_to_end()] == ["out_tokens_per_s",
                                                      "setup_s"]
    rooflines = [m["name"] for m in cell.per_layer()
                 if "roofline" in m["name"] or "mfu" in m["name"]]
    assert sorted(rooflines) == [
        "dsa_index_roofline.longctx", "mla_attend_roofline.longctx",
        "mla_chunk_attend_roofline.longctx",
        "moe_experts_roofline.longctx", "serve_mfu_pct.longctx"]
