"""The readers that ``smallthinker_21b.mixed_closed`` adds, on a planted
trace, hand-made spans and snapshots, as ``test_readers_sala.py`` does for
its cell: the share and roofline readers find the new scopes inside the
decode program, the counter readers take the window's share, and every one
reads nothing (and does not raise) where the program has no such scope,
span or counter."""
import pytest

from perfbench.harness import (flops_smallthinker, readers_sala,
                               readers_smallthinker, scopes, spec, tracing)

CELL = "smallthinker_21b.mixed_closed"
PEAKS = spec.load_json(spec.BENCH_DIR, "peaks.json")["devices"]["TPU v5 lite"]


class _Capture:
    sync = [100.0, 110.0]

    def path(self):
        return "unused"


def _trace():
    """Two decode steps (1.0-1.02, 2.0-2.02) and one chunk (3.0-3.1) on the
    trace's clock; the window is 0 .. 10 there, 100 .. 110 on the host's."""
    modules = [("jit__step_paged(1)", 1.0, 0.02),
               ("jit__step_paged(1)", 2.0, 0.02),
               ("jit__prefill_chunk(2)", 3.0, 0.1)]
    busy = [(1.0, 1.02), (2.0, 2.02), (3.0, 3.1)]
    return {"t0": 0.0, "t1": 10.0, "window_s": 10.0, "busy_s": 0.14,
            "devices": {"/device:TPU:0": {"ops": [], "busy": busy,
                                          "modules": modules}}}


def _ops():
    def op(a, b, *names):
        return ("%fusion.1 = f32[4] fusion(...)", a, b, names, False)

    def ragged(a, b):   # as the chip's trace has them: no op_name, no scope
        return ("%ragged-dot-none.3 = f32[192,768]{1,0} custom-call(...)",
                a, b, (), False)
    return [op(1.000, 1.001, "moe_route"), op(1.001, 1.004, "attn_full"),
            op(1.004, 1.008, "attn_window"), op(1.008, 1.010, "moe_experts"),
            ragged(1.010, 1.019),
            op(2.000, 2.001, "moe_route"), op(2.001, 2.003, "attn_full"),
            op(2.003, 2.008, "attn_window"), op(2.008, 2.010, "moe_experts"),
            ragged(2.010, 2.019),
            op(3.000, 3.020, "attn_full"), op(3.020, 3.040, "attn_window"),
            op(3.040, 3.050, "moe_experts"), ragged(3.050, 3.090),
            op(3.090, 3.095, "head"),
            ("%copy.7 = bf16[64] copy(...)", 3.095, 3.100, (), False)]


def _log():
    """Three streams decoding through both steps (contexts 12,000 / 900 /
    5,000 and a few tokens), one that ended before, one whose first token
    comes later."""
    def stream(prompt, first, last):
        return {"prompt": [1] * prompt, "done": True,
                "token_times": [first, first + 0.5, last]}
    return [stream(12000, 100.5, 108.0), stream(900, 100.2, 109.0),
            stream(5000, 99.0, 107.0), stream(9000, 96.0, 100.9),
            stream(9000, 104.0, 109.0)]


def _snap(touched, load, steps, assignments, window_pages, occupied,
          pending=1):
    return {"slots_occupied": occupied, "prefills_pending": pending,
            "moe_experts_touched": {"mean": touched, "window": steps},
            "moe_expert_load": {"mean": load, "window": steps},
            "moe_assignments_total": assignments,
            "kv_pages_in_use": {"full": 3000, "window": window_pages}}


KEPT = [(90.0, _snap(30.0, 5.0, 100, 100 * 8 * 60, 500, 10)),   # the ramp
        (96.0, _snap(40.0, 4.0, 200, 200 * 8 * 90, 1000, 20)),
        (120.0, _snap(55.0, 3.2, 1000, 9e5, 1500, 27, pending=0)),
        (134.0, _snap(58.0, 3.0, 2200, 200 * 8 * 90 + 2000 * 8 * 168, 1740,
                      28)),
        (140.0, _snap(60.0, 2.9, 9000, 9e7, 1900, 30))]


def _ctx(monkeypatch):
    monkeypatch.setattr(scopes, "device_ops", lambda *a: _ops())
    cell = spec.Cell(CELL)
    monkeypatch.setattr(cell.adapter(), "SNAPSHOTS", KEPT)
    return {"cell": cell, "peaks": PEAKS, "trace": _trace(),
            "capture": _Capture(), "t0": 95.0, "t1": 135.0, "log": _log()}


def test_shares_read_the_new_scopes(monkeypatch):
    ctx = _ctx(monkeypatch)
    busy = 2 * 0.019 + 0.1          # the operations' own time
    assert spec.metric_reader("moe_share_pct.mixed")(dict(ctx)) == \
        pytest.approx(100 * (0.002 + 0.022 + 0.050) / busy)
    assert spec.metric_reader("attn_full_share_pct.mixed")(dict(ctx)) == \
        pytest.approx(100 * 0.025 / busy)
    assert spec.metric_reader("attn_window_share_pct.mixed")(dict(ctx)) == \
        pytest.approx(100 * 0.029 / busy)
    # the compiler's grouped products are the experts', not unscoped time
    assert spec.metric_reader("unscoped_share_pct.mixed")(dict(ctx)) == \
        pytest.approx(100 * 0.005 / busy)
    assert spec.metric_reader("prefill_chunk_ms.mixed")(ctx) == \
        pytest.approx(100.0)
    assert spec.metric_reader("decode_step_ms.mixed")(ctx) == \
        pytest.approx(20.0)


def test_counter_readers_take_the_windows_share(monkeypatch):
    ctx = _ctx(monkeypatch)
    got = spec.metric_reader("moe_experts_touched_mean.mixed")(ctx)
    assert got == pytest.approx((58.0 * 2200 - 40.0 * 200) / 2000) == 59.8
    got = spec.metric_reader("moe_load_max_over_mean.mixed")(ctx)
    assert got == pytest.approx((3.0 * 2200 - 4.0 * 200) / 2000) == 2.9
    # the three snapshots inside the window; a prompt being fed counts
    want = (1000 / 21 + 1500 / 27 + 1740 / 29) / 3
    assert spec.metric_reader("kv_pages_window_mean.mixed")(ctx) == \
        pytest.approx(want)


def test_rooflines_count_the_decode_programs_time_and_the_live_streams(
        monkeypatch):
    ctx = _ctx(monkeypatch)
    cfg = ctx["cell"].config
    # without the compiler's kernels the scope reads 2 ms a step; with
    # them 11; the chunk's 50 ms under moe_experts are not decode's
    assert readers_sala.seconds_in_decode(dict(ctx), ("moe_experts",)) == \
        pytest.approx(0.004)
    readers_smallthinker.rebooked(ctx)
    assert readers_sala.seconds_in_decode(ctx, ("moe_experts",)) == \
        pytest.approx(0.022)
    # 59.8 experts touched a layer a step, 168 assignments: bytes bound
    least = 8 * 59.8 * flops_smallthinker.expert_params(cfg) * 2 / 819e9
    got = spec.metric_reader("moe_experts_roofline.mixed")(ctx)
    assert got == pytest.approx(100 * 2 * least / 0.022) and 0 < got < 100
    assert readers_sala.live_contexts(ctx, 101.0) == [12002, 902, 5002]
    read = sum(flops_smallthinker.kv_read_bytes(cfg, c)
               for c in (12002, 902, 5002))
    got = spec.metric_reader("paged_attn_roofline.mixed")(ctx)
    assert got == pytest.approx(100 * (2 * read / 819e9) / 0.014)
    assert 0 < got < 100


def test_serve_mfu_counts_the_requests_that_finished_in_the_window(
        monkeypatch):
    ctx = _ctx(monkeypatch)
    cfg = ctx["cell"].config
    total = 0
    for p in (12000, 900, 5000, 9000, 9000):   # all five ended in 95 .. 135
        total += flops_smallthinker.forward_flops(cfg, p - 1, p / 2, False)
        total += flops_smallthinker.forward_flops(cfg, 3, p + 1.5, True)
    got = spec.metric_reader("serve_mfu_pct.mixed")(ctx)
    assert got == pytest.approx(100 * total / 40 / 197e12) and 0 < got < 100


@pytest.mark.parametrize("name", [
    "moe_share_pct", "attn_window_share_pct", "attn_full_share_pct",
    "moe_experts_touched_mean", "moe_load_max_over_mean",
    "kv_pages_window_mean", "moe_experts_roofline", "paged_attn_roofline",
    "decode_steps_per_chunk", "prefill_chunk_ms", "prefill_chunk_share_pct",
    "decode_step_ms", "unscoped_share_pct", "device_idle_pct",
    "serve_host_ms_per_step", "serve_post_ms_per_step",
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
    assert spec.metric_reader(name + ".mixed")(ctx) is None


def test_every_metric_of_the_cell_has_a_reader():
    for m in spec.Cell(CELL).per_layer():
        assert callable(spec.metric_reader(m["name"]))
    assert len(spec.Cell(CELL).per_layer()) == 22
