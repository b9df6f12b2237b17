"""The readers that ``minicpm_sala.longdoc_closed`` adds, on a planted
trace, hand-made spans and snapshots, as ``test_scopes.py`` does for the
older cells: the share and roofline readers find the new scopes inside the
decode program, the counter readers take the window's share, and every one
reads nothing (and does not raise) where the program has no such scope,
span or counter."""
import pytest

from perfbench.harness import flops_sala, readers_sala, scopes, spec, tracing

CELL = "minicpm_sala.longdoc_closed"
PEAKS = spec.load_json(spec.BENCH_DIR, "peaks.json")["devices"]["TPU v5 lite"]


class _Capture:
    sync = [100.0, 110.0]

    def path(self):
        return "unused"


def _trace():
    """Two decode steps (1.0-1.02, 2.0-2.02) and one chunk (3.0-3.3) on the
    trace's clock; the window is 0 .. 10 there, 100 .. 110 on the host's."""
    modules = [("jit__step_paged(1)", 1.0, 0.02),
               ("jit__step_paged(1)", 2.0, 0.02),
               ("jit__prefill_chunk(2)", 3.0, 0.3)]
    busy = [(1.0, 1.02), (2.0, 2.02), (3.0, 3.3)]
    return {"t0": 0.0, "t1": 10.0, "window_s": 10.0, "busy_s": 0.34,
            "devices": {"/device:TPU:0": {"ops": [], "busy": busy,
                                          "modules": modules}}}


def _ops():
    def op(a, b, *names):
        return ("%fusion.1 = f32[4] fusion(...)", a, b, names, False)
    return [op(1.000, 1.002, "linear_attn"), op(1.002, 1.006, "sparse_attend"),
            op(1.006, 1.007, "sparse_select"), op(1.007, 1.020, "ffn"),
            op(2.000, 2.002, "linear_attn"), op(2.002, 2.005, "sparse_attend"),
            op(2.005, 2.006, "kv_compress"), op(2.006, 2.020, "ffn"),
            op(3.000, 3.030, "linear_attn"), op(3.030, 3.060, "sparse_attend"),
            op(3.060, 3.300, "ffn")]


def _ctx(monkeypatch, log=None):
    monkeypatch.setattr(scopes, "device_ops", lambda *a: _ops())
    return {"cell": spec.Cell(CELL), "peaks": PEAKS, "trace": _trace(),
            "capture": _Capture(), "t0": 95.0, "t1": 135.0,
            "log": log if log is not None else _log()}


def _log():
    """Three streams decoding through both steps (contexts 12,000 / 9,000 /
    20,000 and a few tokens), one that ended before, one whose first token
    comes later."""
    def stream(prompt, first, last):
        return {"prompt": [1] * prompt, "done": True,
                "token_times": [first, first + 0.5, last]}
    return [stream(12000, 100.5, 108.0), stream(9000, 100.2, 109.0),
            stream(20000, 99.0, 107.0), stream(9000, 96.0, 100.9),
            stream(9000, 104.0, 109.0)]


def test_shares_read_the_new_scopes(monkeypatch):
    ctx = _ctx(monkeypatch)
    assert spec.metric_reader("linear_attn_share_pct.longdoc")(ctx) == \
        pytest.approx(100 * 0.034 / 0.34)
    assert spec.metric_reader("sparse_attn_share_pct.longdoc")(dict(ctx)) == \
        pytest.approx(100 * 0.039 / 0.34)
    assert spec.metric_reader("prefill_chunk_share_pct.longdoc")(ctx) == \
        pytest.approx(100 * 0.3 / 0.34)
    assert spec.metric_reader("prefill_chunk_ms.longdoc")(ctx) == \
        pytest.approx(300.0)
    assert spec.metric_reader("decode_step_ms.longdoc")(ctx) == \
        pytest.approx(20.0)


def test_rooflines_count_the_decode_programs_time_and_the_live_streams(
        monkeypatch):
    ctx = _ctx(monkeypatch)
    # the chunk's 30 ms under linear_attn are not decode's
    assert readers_sala.seconds_in_decode(ctx, ("linear_attn",)) == \
        pytest.approx(0.004)
    assert readers_sala.live_contexts(ctx, 101.0) == [12002, 9002, 20002]
    cfg = ctx["cell"].config
    state = 3 * 12 * flops_sala.linear_state_bytes(cfg)   # a step
    want = 100 * (2 * state / 819e9) / 0.004
    got = spec.metric_reader("linear_attn_roofline.longdoc")(ctx)
    assert got == pytest.approx(want) and 0 < got < 100
    read = 4 * sum(flops_sala.sparse_read_bytes(cfg, c)
                   for c in (12002, 9002, 20002))
    want = 100 * (2 * read / 819e9) / 0.009
    got = spec.metric_reader("sparse_attn_roofline.longdoc")(ctx)
    assert got == pytest.approx(want) and 0 < got < 100


def test_counter_readers_take_the_windows_share(monkeypatch):
    ctx = _ctx(monkeypatch)
    adapter = ctx["cell"].adapter()

    def snap(chunks, steps, mean, count):
        return {"prefill_chunks_total": chunks,
                "steps_between_chunks_total": steps,
                "sparse_positions_read": {"mean": mean, "window": count}}
    kept = [(90.0, snap(50, 10, 8192.0, 100)),     # ramp: not the window's
            (96.0, snap(60, 20, 8000.0, 200)),
            (120.0, snap(100, 90, 7000.0, 1000)),
            (134.0, snap(160, 216, 6560.0, 2400)),
            (140.0, snap(900, 900, 6000.0, 9000))]
    monkeypatch.setattr(adapter, "SNAPSHOTS", kept)
    assert spec.metric_reader("decode_steps_per_chunk.longdoc")(ctx) == \
        pytest.approx((216 - 20) / (160 - 60))
    got = spec.metric_reader("sparse_positions_read_mean.longdoc")(ctx)
    assert got == pytest.approx((6560.0 * 2400 - 8000.0 * 200) / 2200)


@pytest.mark.parametrize("name", [
    "linear_attn_share_pct", "sparse_attn_share_pct", "linear_attn_roofline",
    "sparse_attn_roofline", "decode_steps_per_chunk",
    "sparse_positions_read_mean", "prefill_chunk_ms",
    "prefill_chunk_share_pct", "decode_step_ms", "unscoped_share_pct",
    "device_idle_pct", "serve_host_ms_per_step", "serve_post_ms_per_step",
    "queue_host_ms_per_step", "compiles_in_window"])
def test_a_program_without_the_scope_span_or_counter_reads_nothing(
        monkeypatch, name):
    """The parent commit beside this benchmark: no trace, no spans of the
    program, snapshots without the new counters. No reader raises."""
    adapter = spec.Cell(CELL).adapter()
    monkeypatch.setattr(adapter, "SNAPSHOTS",
                        [(96.0, {"slots_occupied": 1}),
                         (120.0, {"slots_occupied": 2})])
    ctx = {"cell": spec.Cell(CELL), "peaks": PEAKS, "trace": None,
           "capture": None, "t0": 95.0, "t1": 135.0, "log": [],
           "spans": tracing.HostSpans(),
           "device": {"memory_peak_bytes": 0}}
    assert spec.metric_reader(name + ".longdoc")(ctx) is None


def test_serve_mfu_counts_the_requests_that_finished_in_the_window():
    cell = spec.Cell(CELL)
    log = [{"prompt": [1] * 10000, "done": True,
            "token_times": [100.0 + i for i in range(20)]},
           {"prompt": [1] * 9000, "done": True, "token_times": [200.0]}]
    ctx = {"cell": cell, "peaks": PEAKS, "t0": 95.0, "t1": 135.0, "log": log}
    cfg = cell.config
    total = flops_sala.forward_flops(cfg, 9999, 5000.0, False) \
        + flops_sala.forward_flops(cfg, 20, 10010.0, True)
    assert readers_sala.serve_mfu_pct(ctx) == pytest.approx(
        100 * total / 40 / 197e12)
    assert 0.5 < readers_sala.serve_mfu_pct(ctx) < 2
