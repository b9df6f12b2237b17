"""The two readers that PR 30 adds for ``gpt2_small.doc_closed``, on
hand-made spans: writes a step counts the program's ``serve.put_result``
spans over its ``serve.step`` spans inside the traced stretch, the publish
lag is the 95th percentile of ``serve.publish_lag`` over the window, and
each reads nothing (and does not raise) where the program emits no such
span or the run was not traced."""
import pytest

from perfbench.harness import spec, tracing

CELL = "gpt2_small.doc_closed"


class _Capture:
    sync = [100.0, 105.0]


def _ctx(spans, capture=_Capture()):
    heard = tracing.HostSpans()
    for span in spans:
        heard.add(*span)
    return {"cell": spec.Cell(CELL), "spans": heard, "capture": capture,
            "t0": 98.0, "t1": 138.0}


def _steps(n, start=100.0, every=0.01):
    return [("serve.step", start + i * every, 0.008) for i in range(n)]


def _read(name, ctx):
    return spec.metric_reader(name)(ctx)


@pytest.mark.parametrize("writes_a_step, expected", [(31, 31.0), (6, 6.0)],
                         ids=["a_write_a_stream", "newest_record"])
def test_writes_a_step_counts_writes_over_steps(writes_a_step, expected):
    steps = _steps(100)
    writes = [("serve.put_result", s + 0.0001 * k, 0.001)
              for _, s, _ in steps for k in range(writes_a_step)]
    # outside the traced stretch: counted on neither side
    outside = [("serve.step", 99.0, 0.008), ("serve.put_result", 106.0, 0.001)]
    got = _read("result_writes_per_step.doc", _ctx(steps + writes + outside))
    assert got == pytest.approx(expected)


def test_writes_a_step_reads_nothing_without_its_spans():
    name = "result_writes_per_step.doc"
    assert _read(name, _ctx(_steps(10))) is None
    assert _read(name, _ctx([("serve.put_result", 101.0, 0.001)])) is None
    assert _read(name, _ctx(_steps(10) + [("serve.put_result", 101.0, 0.001)],
                            capture=None)) is None


def test_publish_lag_is_the_tail_of_the_programs_spans():
    lags = [("serve.publish_lag", 100.0 + 0.1 * i, 0.001 * (i + 1))
            for i in range(100)]
    early = [("serve.publish_lag", 90.0, 5.0)]   # before the window opened
    got = _read("publish_lag_p95_ms.doc", _ctx(lags + early + _steps(10)))
    assert got == pytest.approx(95.0)
    # a program without a publisher emits none: nothing to read, no fault
    assert _read("publish_lag_p95_ms.doc", _ctx(_steps(10))) is None


def test_the_cell_lists_both_and_they_move_its_rate():
    by_name = {m["name"]: m for m in spec.Cell(CELL).per_layer()}
    for name in ("result_writes_per_step.doc", "publish_lag_p95_ms.doc"):
        assert by_name[name]["moves"] == "out_tokens_per_s"
        assert by_name[name]["better"] == "lower"
        assert by_name[name]["workloads"] == [CELL]
    other = {m["name"] for m in spec.Cell("gpt2_small.chat_open").per_layer()}
    assert not other & {"result_writes_per_step.doc", "publish_lag_p95_ms.doc"}
