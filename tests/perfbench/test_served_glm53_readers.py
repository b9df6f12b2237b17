"""The readers that ``glm_5_3_flash.longgen_closed`` adds, on a planted
trace, hand-made spans and snapshots, as the ``glm5`` readers' test does for
its cell: the shares find the KDA scopes and the streams' mixing in decode
steps and chunks alike, the whole step's share counts every kind of layer,
every
metric of the cell has a reader, and each new one reads nothing (and does
not raise) where the program has no such scope or counter."""
import pytest

from perfbench.harness import flops_glm53, scopes, spec

CELL = "glm_5_3_flash.longgen_closed"
PEAKS = spec.load_json(spec.BENCH_DIR, "peaks.json")["devices"]["TPU v5 lite"]
NEW = ("serve_mfu_pct.longgen", "delta_attn_share_pct.longgen",
       "mhc_share_pct.longgen")


class _Capture:
    sync = [100.0, 110.0]

    def path(self):
        return "unused"


def _trace():
    """Two decode steps (1.0-1.03, 2.0-2.03) and one chunk (3.0-3.2) on the
    trace's clock; the window is 0 .. 10 there, 100 .. 110 on the host's."""
    modules = [("jit__step_paged(1)", 1.0, 0.03),
               ("jit__step_paged(1)", 2.0, 0.03),
               ("jit__prefill_chunk(2)", 3.0, 0.2)]
    busy = [(1.0, 1.03), (2.0, 2.03), (3.0, 3.2)]
    return {"t0": 0.0, "t1": 10.0, "window_s": 10.0, "busy_s": 0.26,
            "devices": {"/device:TPU:0": {"ops": [], "busy": busy,
                                          "modules": modules}}}


def _ops():
    def op(a, b, *names):
        return ("%fusion.1 = f32[4] fusion(...)", a, b, names, False)

    out = []
    for start in (1.0, 2.0):
        out += [op(start, start + 0.002, "mhc"),
                op(start + 0.002, start + 0.003, "delta_conv"),
                op(start + 0.003, start + 0.008, "delta_attn"),
                op(start + 0.008, start + 0.020, "moe_experts"),
                op(start + 0.020, start + 0.030, "mla_attend")]
    return out + [op(3.000, 3.010, "mhc"), op(3.010, 3.030, "delta_conv"),
                  op(3.030, 3.110, "delta_attn"),
                  op(3.110, 3.200, "moe_experts")]


def _log():
    """Three streams decoding through both steps, one that ended before,
    one whose first token comes later."""
    def stream(prompt, first, last, tokens=3):
        times = [first + (last - first) * i / (tokens - 1)
                 for i in range(tokens)]
        return {"prompt": [1] * prompt, "done": True, "token_times": times}
    return [stream(2000, 100.5, 108.0), stream(900, 100.2, 109.0),
            stream(16000, 99.0, 107.0), stream(3000, 96.0, 100.9),
            stream(1500, 104.0, 109.0)]


def _snap(steps, assignments, chunks, fed, touched=20.0):
    return {"slots_occupied": 60, "prefills_pending": 1,
            "decode_steps_total": steps, "prefill_chunks_total": chunks,
            "prompt_tokens_total": fed, "steps_between_chunks_total": steps,
            "moe_experts_touched": {"mean": touched, "window": steps},
            "moe_expert_load": {"mean": 2.0, "window": steps},
            "moe_assignments_total": assignments,
            "sparse_positions_read": {"mean": 1900.0, "window": steps},
            "dsa_blocks_scored": {"mean": 900.0, "window": steps}}


KEPT = [(96.0, _snap(200, 12000, 4, 6000)),
        # two inside the traced stretch (100 .. 110): 1 chunk of 1,500
        (100.5, _snap(400, 12030, 7, 9000)),
        (109.5, _snap(402, 12060, 8, 10500)),
        (134.0, _snap(2200, 12090, 20, 30000))]


def _ctx(monkeypatch, ops=_ops):
    monkeypatch.setattr(scopes, "device_ops", lambda *a: ops())
    cell = spec.Cell(CELL)
    monkeypatch.setattr(cell.adapter(), "SNAPSHOTS", KEPT)
    return {"cell": cell, "peaks": PEAKS, "trace": _trace(),
            "capture": _Capture(), "t0": 95.0, "t1": 135.0, "log": _log(),
            "device": {"memory_peak_bytes": 13e9}, "slot_samples": [],
            "spans": None}


def test_every_metric_of_the_cell_has_a_reader():
    cell = spec.Cell(CELL)
    names = {m["name"] for m in cell.per_layer()}
    assert set(NEW) <= names
    for name in names:
        assert callable(spec.metric_reader(name))
    assert {m["name"] for m in cell.end_to_end()} == {"out_tokens_per_s",
                                                      "setup_s"}


@pytest.mark.parametrize("name,seconds", [
    # a step: 1 ms under delta_conv and 5 under delta_attn; the chunk 20
    # and 80
    ("delta_attn_share_pct.longgen", 2 * 0.006 + 0.100),
    # a step 2 ms, the chunk 10
    ("mhc_share_pct.longgen", 2 * 0.002 + 0.010)])
def test_a_share_reads_its_scopes_in_steps_and_chunks(monkeypatch, name,
                                                       seconds):
    ctx = _ctx(monkeypatch)
    got = spec.metric_reader(name)(dict(ctx))
    assert got == pytest.approx(100 * seconds / 0.26)


def test_the_whole_steps_share_counts_the_finished_requests(monkeypatch):
    ctx = _ctx(monkeypatch)
    cfg = ctx["cell"].config
    got = spec.metric_reader("serve_mfu_pct.longgen")(dict(ctx))
    # 90 assignments between the first and the last snapshot of the window
    # (96.0 .. 134.0), over the 15 tokens that came between them, 4 layers
    counted = 90 / 15 / 4
    total = 0
    for r in _log():
        if not 95.0 <= r["token_times"][-1] < 135.0:
            continue
        p, o = len(r["prompt"]), len(r["token_times"])
        total += flops_glm53.forward_flops(cfg, p - 1, p / 2, False)
        total += flops_glm53.forward_flops(cfg, o, p + o / 2, True, counted)
    assert got == pytest.approx(100 * total / 40.0 / PEAKS["bf16_flops"])
    assert 0 < got < 100


def test_nothing_to_read_reads_none(monkeypatch):
    """A program without the KDA scopes (the parent's), a run without a
    trace, no snapshots: every new reader gives ``None``."""
    ctx = _ctx(monkeypatch, ops=lambda: [
        ("%fusion.1 = f32[4] fusion(...)", 1.0, 1.02, ("moe_experts",),
         False)])
    for name in ("delta_attn_share_pct.longgen", "mhc_share_pct.longgen"):
        assert spec.metric_reader(name)(dict(ctx)) is None
    bare = dict(ctx, trace=None, capture=None, log=[])
    for name in NEW:
        assert spec.metric_reader(name)(dict(bare)) is None
