"""The two readers that PR 32 adds for ``gpt2_small.doc_closed``, on
hand-made spans: the share of steps
dispatched ahead counts the program's ``serve.step_ahead`` spans over its
``serve.step`` spans inside the traced stretch, the fetch's wait is the
time in ``profile.serving.fetch`` a ``serve.step``, and each reads nothing
(and does not raise) where the program emits no such span (a serial loop:
the parent) or the run was not traced."""
import pytest

from perfbench.harness import spec, tracing

CELLS = {"doc": "gpt2_small.doc_closed"}


class _Capture:
    sync = [100.0, 105.0]


def _ctx(cell, spans, capture=_Capture()):
    heard = tracing.HostSpans()
    for span in spans:
        heard.add(*span)
    return {"cell": spec.Cell(CELLS[cell]), "spans": heard,
            "capture": capture, "t0": 98.0, "t1": 138.0}


def _steps(n, start=100.0, every=0.01):
    return [("serve.step", start + i * every, 0.006) for i in range(n)]


def _read(name, ctx):
    return spec.metric_reader(name)(ctx)


@pytest.mark.parametrize("cell", sorted(CELLS))
@pytest.mark.parametrize("ahead, expected", [(99, 99.0), (50, 50.0)],
                         ids=["steady", "half"])
def test_the_share_counts_steps_ahead_over_steps(cell, ahead, expected):
    steps = _steps(100)
    spans = [("serve.step_ahead", s + 0.002, 0.009)
             for _, s, _ in steps[:ahead]]
    # outside the traced stretch: counted on neither side
    outside = [("serve.step", 99.0, 0.006), ("serve.step_ahead", 106.0, 0.009)]
    got = _read(f"steps_ahead_share_pct.{cell}",
                _ctx(cell, steps + spans + outside))
    assert got == pytest.approx(expected)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_share_reads_nothing_from_a_serial_loop(cell):
    name = f"steps_ahead_share_pct.{cell}"
    assert _read(name, _ctx(cell, _steps(10))) is None
    assert _read(name, _ctx(cell, [("serve.step_ahead", 101.0, 0.01)])) is None
    assert _read(name, _ctx(cell, _steps(10)
                            + [("serve.step_ahead", 101.0, 0.01)],
                            capture=None)) is None


@pytest.mark.parametrize("cell", sorted(CELLS))
@pytest.mark.parametrize("wait_ms", [2.5, 0.3], ids=["serial", "ahead"])
def test_the_fetchs_wait_is_its_spans_time_a_step(cell, wait_ms):
    steps = _steps(100)
    fetches = [("profile.serving.fetch", s + 0.003, wait_ms / 1e3)
               for _, s, _ in steps]
    early = [("profile.serving.fetch", 99.0, 0.5)]  # before the stretch
    got = _read(f"fetch_wait_ms_per_step.{cell}",
                _ctx(cell, steps + fetches + early))
    assert got == pytest.approx(wait_ms)
    name = f"fetch_wait_ms_per_step.{cell}"
    assert _read(name, _ctx(cell, _steps(10))) is None
    assert _read(name, _ctx(cell, steps + fetches, capture=None)) is None


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_each_cell_lists_its_two_and_they_move_its_rate(cell):
    by_name = {m["name"]: m for m in spec.Cell(CELLS[cell]).per_layer()}
    for name, better in ((f"steps_ahead_share_pct.{cell}", "higher"),
                         (f"fetch_wait_ms_per_step.{cell}", "lower")):
        assert by_name[name]["moves"] == "out_tokens_per_s"
        assert by_name[name]["better"] == better
        assert by_name[name]["source"] == "program_span"
        assert by_name[name]["workloads"] == [CELLS[cell]]
        assert by_name[name]["layer"] == \
            by_name[f"serve_host_ms_per_step.{cell}"]["layer"]
    # no other cell lists them (``smallthinker_21b.mixed_closed`` pins its
    # count of metrics in a test of its own: PERF.md, Open questions)
    for other in ("gpt2_small.chat_open", "smallthinker_21b.mixed_closed"):
        names = {m["name"] for m in spec.Cell(other).per_layer()}
        assert not any(n.startswith(("steps_ahead_share_pct",
                                     "fetch_wait_ms_per_step"))
                       for n in names)
