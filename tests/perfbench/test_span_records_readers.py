"""``harness/records.py`` and the eight readers that PR 35 builds on it, on
planted records: children by ``parent``, self time, records by ``request``,
sums of an ``arg``, the serve loop's lane; each reader's number from records
made by hand; and ``None`` (no raise) from a context whose program keeps no
records, whose run was not traced, or that holds no record of the name."""
import collections

import pytest

from perfbench.harness import records, spec

DOC, LONGDOC = "gpt2_small.doc_closed", "minicpm_sala.longdoc_closed"

#: the program's record, field for field (common/utils.py SpanRecord)
Rec = collections.namedtuple(
    "Rec", "name start seconds lane id parent request args")
Rec.__new__.__defaults__ = (None, ())


class _Capture:
    sync = [100.0, 105.0]


def _ctx(cell, recs, capture=_Capture()):
    return {"cell": spec.Cell(cell), "records": recs, "capture": capture,
            "t0": 98.0, "t1": 138.0}


def _read(name, ctx):
    return spec.metric_reader(name)(ctx)


def _iteration(first_id, start, claim=1.4, join=1.1, dispatch=1.7,
               evict=0.3, rest=0.2, lane="srv1-loop"):
    """One ``serve.step`` of the loop with its tree, lengths in ms:
    expire, admit (claim, join (host_input)), prepare, dispatch, evict,
    fetch, post, and ``rest`` ms that no child covers."""
    ms, out, at = 1e-3, [], start
    ids = iter(range(first_id + 1, first_id + 20))

    def block(name, length, parent, **more):
        nonlocal at
        rec = Rec(name, at, length * ms, lane, next(ids), parent, **more)
        out.append(rec)
        return rec
    block("serve.expire", 0.02, first_id)
    at += 0.02 * ms
    admit = block("serve.admit", claim + join + 0.05, first_id)
    at += 0.01 * ms
    block("serve.claim", claim, admit.id)
    at += claim * ms
    joined = block("serve.join", join, admit.id, request=7,
                   args=(("bucket", 512),))
    block("profile.serving.host_input", join - 0.1, joined.id)
    at = admit.start + admit.seconds
    block("serve.prepare", 0.03, first_id)
    at += 0.03 * ms
    block("profile.serving.dispatch", dispatch, first_id)
    at += dispatch * ms
    block("serve.evict", evict, first_id)
    at += evict * ms
    block("profile.serving.fetch", 0.08, first_id)
    at += 0.08 * ms
    block("serve.post", 0.09, first_id)
    at += (0.09 + rest) * ms
    out.append(Rec("serve.step", start, at - start, lane, first_id, None))
    return out


def _doc_records(n=50):
    recs = []
    for i in range(n):
        recs += _iteration(1000 + 20 * i, 100.0 + 0.01 * i)
    # the publisher's lane, and stretches of requests' lives: in no tree
    recs += [Rec("serve.put_result", 100.0 + 0.01 * i, 0.0014,
                 "srv1-publisher", 9000 + i, None, request=7)
             for i in range(n)]
    recs += [Rec("serve.publish_lag", 100.0 + 0.01 * i, 0.02, None,
                 9500 + i, None, request=7) for i in range(n)]
    # before the traced stretch: counted nowhere
    recs += _iteration(10, 99.0, claim=50.0, join=50.0, dispatch=50.0,
                       evict=50.0, rest=50.0)
    # an idle iteration's claim: under no serve.step
    recs += [Rec("serve.admit", 101.0, 0.004, "srv1-loop", 9990, None),
             Rec("serve.claim", 101.0, 0.003, "srv1-loop", 9991, 9990)]
    return recs


# -- harness/records.py -----------------------------------------------------------

def test_children_descendants_and_self_time_by_parent():
    recs = _iteration(100, 100.0, rest=0.25)
    kids = records.children(recs)
    step = recs[-1]
    assert [c.name for c in kids[step.id]] == [
        "serve.expire", "serve.admit", "serve.prepare",
        "profile.serving.dispatch", "serve.evict", "profile.serving.fetch",
        "serve.post"]
    assert {r.name for r in records.descendants(step, kids)} == {
        r.name for r in recs} - {"serve.step"}
    assert records.self_seconds(step, kids) == pytest.approx(0.25e-3)
    admit = kids[step.id][1]
    assert records.self_seconds(admit, kids) == pytest.approx(0.05e-3)
    # a stretch offered after the fact may lie over a sibling (a compile
    # inside the dispatch): the union is taken out, not the sum
    dispatch = next(r for r in recs if r.name == "profile.serving.dispatch")
    over = recs + [Rec("compile.backend", dispatch.start + 1e-4, 1e-3,
                       "srv1-loop", 999, step.id)]
    assert records.self_seconds(step, records.children(over)) == \
        pytest.approx(0.25e-3)


def test_records_by_request_args_and_the_loops_lane():
    recs = _doc_records(3)
    mine = records.by_request(recs)[7]
    assert {r.name for r in mine} == {"serve.join", "serve.put_result",
                                      "serve.publish_lag"}
    join = next(r for r in recs if r.name == "serve.join")
    assert records.arg(join, "bucket") == 512
    assert records.arg(join, "rows") is None
    chunks = [Rec("serve.prefill_chunk", 1.0, 0.1, "l", i, None, 7,
                  (("rows", rows), ("width", 2048), ("name", "text")))
              for i, rows in enumerate((2048, 2048, 700))]
    assert records.arg_sum(chunks, "rows") == 4796
    assert records.arg_sum(chunks, "name") == 0  # no number there
    assert records.lane_of(recs) == "srv1-loop"
    assert records.lane_of([r for r in recs if r.lane is None]) is None
    assert [r.id for r in records.starting_in(
        recs, "serve.step", 100.0, 100.015)] == [1000, 1020]


def test_the_records_come_from_the_program_once_a_context():
    from analytics_zoo_tpu.common import utils as program
    heard = []
    hook = lambda *triple: heard.append(triple)  # noqa: E731
    program.span_hooks.append(hook)
    try:
        with program.time_it("planted.by.the.test"):
            pass
    finally:
        program.span_hooks.remove(hook)
    ctx = {}
    got = records.of(ctx)
    assert got[-1].name == "planted.by.the.test" and heard
    assert records.of(ctx) is got  # fetched once


def test_a_program_without_records_reads_none(monkeypatch):
    from analytics_zoo_tpu.common import utils as program
    monkeypatch.delattr(program, "span_records")  # the parent commit
    ctx = _ctx(DOC, None)
    del ctx["records"]
    assert records.of(ctx) is None
    for name in NAMES_DOC:
        assert _read(name, ctx) is None, name
    for name in ("prefill_wait_p95_ms.longdoc",
                 "prefill_chunk_fill_pct.longdoc"):
        assert _read(name, dict(ctx, cell=spec.Cell(LONGDOC))) is None


# -- the five readers of the iteration ----------------------------------------------

NAMES_DOC = ("loop_claim_ms_per_step.doc", "loop_join_ms_per_step.doc",
             "loop_dispatch_ms_per_step.doc", "loop_evict_ms_per_step.doc",
             "loop_self_ms_per_step.doc")


@pytest.mark.parametrize("name, expected", zip(
    NAMES_DOC, (1.4, 1.1, 1.7, 0.3, 0.2)))
def test_an_iterations_parts_a_step(name, expected):
    assert _read(name, _ctx(DOC, _doc_records())) == pytest.approx(expected)


def test_the_parts_add_up_to_the_step():
    ctx = _ctx(DOC, _doc_records())
    recs, steps, kids = records.traced_steps(ctx)
    assert len(steps) == 50
    named = sum(_read(name, ctx) for name in NAMES_DOC)
    others = sum(records.ms_per_step(ctx, n) for n in (
        "serve.expire", "serve.prepare", "profile.serving.fetch",
        "serve.post"))
    admit_self = 0.05
    step = 1e3 * sum(s.seconds for s in steps) / len(steps)
    assert named + others + admit_self == pytest.approx(step)


@pytest.mark.parametrize("name", NAMES_DOC)
def test_an_iterations_reader_reads_nothing_where_there_is_nothing(name):
    recs = _doc_records()
    assert _read(name, _ctx(DOC, recs, capture=None)) is None  # untraced
    assert _read(name, _ctx(DOC, [])) is None
    part = name.split("_")[1]
    # no record of the name at all: nothing, not nought
    if part != "self":
        wanted = {"claim": "serve.claim", "join": "serve.join",
                  "dispatch": "profile.serving.dispatch",
                  "evict": "serve.evict"}[part]
        without = [r for r in recs if r.name != wanted]
        assert _read(name, _ctx(DOC, without)) is None


# -- the three readers of the prefill turn --------------------------------------------

def _waits(ms, start=99.0):
    return [Rec("serve.prefill_wait", start + i, w / 1e3, None, 100 + i,
                None, request=i) for i, w in enumerate(ms)]


def test_the_wait_for_the_turn_is_a_nearest_rank_tail_over_the_window():
    waits = _waits([100.0 * (i + 1) for i in range(20)])
    outside = [Rec("serve.prefill_wait", 97.0, 99.0, None, 1, None, 50),
               Rec("serve.prefill_wait", 139.0, 99.0, None, 2, None, 51)]
    ctx = _ctx(LONGDOC, waits + outside)
    assert _read("prefill_wait_p95_ms.longdoc", ctx) == pytest.approx(1900.0)
    assert _read("prefill_wait_p95_ms.longdoc", _ctx(LONGDOC, [])) is None


def test_the_chunks_fill_is_rows_over_width_in_the_traced_stretch():
    def chunk(i, at, rows, width):
        return Rec("serve.prefill_chunk", at, 0.001, "srv1-loop", i, None, 7,
                   (("start", 0), ("rows", rows), ("width", width),
                    ("index", 0), ("count", 1)))
    chunks = [chunk(1, 100.5, 2048, 2048), chunk(2, 101.5, 2048, 2048),
              chunk(3, 102.5, 1000, 1024),
              chunk(4, 99.0, 1, 2048), chunk(5, 106.0, 1, 2048)]  # outside
    ctx = _ctx(LONGDOC, chunks)
    assert _read("prefill_chunk_fill_pct.longdoc", ctx) == \
        pytest.approx(100.0 * 5096 / 5120)
    assert _read("prefill_chunk_fill_pct.longdoc",
                 _ctx(LONGDOC, chunks, capture=None)) is None
    bare = [c._replace(args=()) for c in chunks]  # spans without args
    assert _read("prefill_chunk_fill_pct.longdoc",
                 _ctx(LONGDOC, bare)) is None


def test_prompts_pending_is_the_mean_of_the_windows_snapshots(monkeypatch):
    cell = spec.Cell(LONGDOC)
    kept = [(97.0, {"prefills_pending": 9}),
            (99.0, {"prefills_pending": 2}),
            (110.0, {"prefills_pending": 3}),
            (120.0, {"prefills_pending": 4}),
            (139.0, {"prefills_pending": 9})]
    monkeypatch.setattr(cell.adapter(), "SNAPSHOTS", kept)
    ctx = dict(_ctx(LONGDOC, []), cell=cell)
    assert _read("prefill_pending_mean.longdoc", ctx) == pytest.approx(3.0)
    monkeypatch.setattr(cell.adapter(), "SNAPSHOTS", [])
    assert _read("prefill_pending_mean.longdoc", ctx) is None
    # a program whose snapshot lacks the key
    monkeypatch.setattr(cell.adapter(), "SNAPSHOTS",
                        [(99.0, {"slots_occupied": 2})])
    assert _read("prefill_pending_mean.longdoc", ctx) is None


def test_each_cell_lists_its_new_metrics_under_the_serve_loops_layer():
    layer = "serve loop (serving/server.py, serving/queues.py)"
    for cell, names in ((DOC, NAMES_DOC), (LONGDOC, (
            "prefill_wait_p95_ms.longdoc", "prefill_pending_mean.longdoc",
            "prefill_chunk_fill_pct.longdoc"))):
        by_name = {m["name"]: m for m in spec.Cell(cell).per_layer()}
        for name in names:
            entry = by_name[name]
            assert entry["layer"] == layer
            assert entry["moves"] == "out_tokens_per_s"
            assert entry["workloads"] == [cell]
            assert entry["better"] == (
                "higher" if name.startswith("prefill_chunk_fill") else "lower")
