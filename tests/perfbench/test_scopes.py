"""``harness/scopes.py`` and ``harness/xplane.py`` on a small recorded
trace, and the readers of the program's spans on spans made by hand.

``data/scopes_small.xplane.pb`` is cut from the chip's trace of
``bert_base.glue_s128.1chip`` (PR 25): five operations of the first device
with the names and the ``tf_op`` stats the chip recorded, and the two
``perfbench_sync`` marks at 1 ms and 2 ms. Their times were moved: a
backward ``ffn`` product (0.2 ms), a forward ``attention`` product (0.1 ms)
under the backward ``attn_short`` kernel (0.3 ms over the same stretch), a
``copy-start`` without any name (0.05 ms), and an ``optimizer`` fusion that
starts 0.1 ms before the window's end and runs 0.3 ms past it."""
import os
import re

import pytest

from conftest import ROOT

from perfbench.harness import scopes, spans, spec, tracing, xplane

SMALL = os.path.join(ROOT, "tests", "perfbench", "data",
                     "scopes_small.xplane.pb")


def _ops():
    sync = tracing.read_planes(SMALL)["sync"]
    assert sync == [0.001, 0.002]
    return scopes.device_ops(SMALL, sync[0], sync[-1])


def test_the_small_trace_by_scope():
    found = scopes.table(_ops())
    got = {k: round(v * 1e3, 6) for k, v in found["scopes"].items()}
    assert got == {"ffn": 0.2, "attention": 0.1, "attn_short": 0.3,
                   "unscoped": 0.05, "optimizer": 0.1}
    # busy is the union: the attention product runs under the kernel
    assert found["busy_s"] == pytest.approx(0.65e-3)


def test_an_operation_across_the_edge_counts_to_the_edge():
    ops = _ops()
    (optimizer,) = [o for o in ops if o[3] == ("optimizer",)]
    assert optimizer[1] == pytest.approx(0.0019)
    assert optimizer[2] == pytest.approx(0.002)
    assert scopes.device_ops(SMALL, 0.0021, 0.003)[0][3] == ("optimizer",)


def test_seconds_under_takes_a_direction():
    ops = _ops()
    block = ("attention", "attn_short")
    assert scopes.seconds_under(ops, block) == pytest.approx(0.3e-3)
    assert scopes.seconds_under(ops, block, backward=True) == \
        pytest.approx(0.3e-3)
    assert scopes.seconds_under(ops, block, backward=False) == \
        pytest.approx(0.1e-3)
    assert scopes.seconds_under(ops, ("kv_write",)) == 0.0


@pytest.mark.parametrize("op_name,want", [
    ("jit(train_step)/transpose(jvp(attention))/dot_general:",
     (("attention",), True)),
    ("jit(train_step)/jvp(layer_norm)/jit(_var)/reduce_sum:",
     (("layer_norm",), False)),
    ("jit(loss)/transpose(jvp())/shard_map/attn_short/pallas_call:",
     (("attn_short",), True)),
    ("jit(_prefill_paged)/kv_write/kv_write/scatter:",
     (("kv_write", "kv_write"), False)),
    ("jit(_step_paged)/attention/kv_gather/jit(_take)/gather:",
     (("attention", "kv_gather"), False)),
    ("jit(_step_paged)/jit(_take)/gather:", ((), False)),
    # the names are the trace's own: one the harness never heard of counts
    ("jit(f)/jvp(rotary)/while/body/mul:", (("rotary",), False)),
    # what JAX puts on a path itself is no scope of the program
    ("jit(_step_paged)/bhtd,bhkd->bhtk/dot_general:", ((), False)),
    ("jit(f)/transpose(jvp(jit(_var)))/while/body/cond/branch_1_fun/add:",
     ((), True)),
    ("caches[11]['k']:", (("arg:caches",), False)),
    ("gather", ((), False)),
    ("", ((), False)), (None, ((), False)),
])
def test_scope_path(op_name, want):
    assert scopes.scope_path(op_name) == want


def test_the_raw_reader_agrees_with_profile_data():
    """Names and times as ``jax.profiler.ProfileData`` gives them, and the
    stats of the events' metadata, which it does not show."""
    planes = tracing.read_planes(SMALL)
    raw = xplane.read(SMALL, planes=lambda n: n.startswith("/device:"))
    (line,) = raw[0]["lines"]
    assert line["name"] == "XLA Ops"
    mine = [(e["metadata"]["name"], e["start_s"], e["seconds"])
            for e in line["events"]]
    theirs = planes["devices"]["/device:TPU:0"]["ops"]
    assert [m[0] for m in mine] == [t[0] for t in theirs]
    for m, t in zip(mine, theirs):
        assert m[1] == pytest.approx(t[1]) and m[2] == pytest.approx(t[2])
    stats = line["events"][0]["metadata"]["stats"]
    assert stats["tf_op"].endswith("transpose(jvp(ffn))/dot_general:")
    assert stats["hlo_category"]


def test_the_programs_scopes_are_the_documented_ones():
    """The harness takes the names from the trace; the list is the
    program's, in docs/observability.md, and holds every name that the
    package gives a ``jax.named_scope``."""
    with open(os.path.join(ROOT, "docs", "observability.md")) as f:
        text = f.read()
    section = text.split("## Scope names on the device")[1].split("\n## ")[0]
    documented = set(re.findall(r"`([a-z_]+)`", section))
    used = set()
    for folder, _, files in os.walk(os.path.join(ROOT, "analytics_zoo_tpu")):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(folder, name)) as f:
                    used |= set(re.findall(
                        r"named_scope\(\s*\"([^\"]+)\"", f.read()))
    assert len(used) >= 15 and used <= documented, sorted(used - documented)


def test_unscoped_is_what_no_scope_of_the_program_names(monkeypatch):
    monkeypatch.setattr(scopes, "device_ops", lambda *a: [
        ("%copy.1 = f32[4] copy(...)", 0.0, 1.0, ("arg:caches",), False),
        ("%copy.2 = f32[4] copy(...)", 1.0, 2.0, (), False),
        ("%fusion.3 = f32[4] fusion(...)", 2.0, 4.0, ("kv_gather",), False),
        ("%fusion.4 = f32[4] fusion(...)", 4.0, 5.0,
         ("attention", "kv_write"), False)])
    ctx = {"trace": {"t0": 0.0, "t1": 5.0}, "capture": _Capture()}
    assert spec.metric_reader("unscoped_share_pct.chat")(ctx) == \
        pytest.approx(40.0)
    assert spec.metric_reader("kv_move_share_pct.chat")(ctx) == \
        pytest.approx(60.0)
    assert spec.metric_reader("kv_move_share_pct.doc")(dict(ctx)) == \
        pytest.approx(60.0)


def test_a_cpu_trace_or_none_reads_nothing():
    class Capture:
        def path(self):
            return SMALL
    assert scopes.of({"trace": None, "capture": Capture()}) is None
    assert scopes.share_pct({"trace": None, "capture": None},
                            ("attention",)) is None
    ctx = {"trace": {"t0": 0.001, "t1": 0.002}, "capture": Capture()}
    assert scopes.share_pct(ctx, ("optimizer",)) == pytest.approx(
        100 * 0.1 / 0.65)
    assert "scoped_ops" in ctx  # read once a run


def test_argument_names_alone_are_a_program_without_scopes(monkeypatch):
    """The parent's decode step: XLA names the copies of the pools after the
    argument, the program names nothing. No scope reader may read it."""
    monkeypatch.setattr(scopes, "device_ops", lambda *a: [
        ("%copy.1 = f32[4] copy(...)", 0.0, 1.0, ("arg:caches",), False),
        ("%fusion.2 = f32[4] fusion(...)", 1.0, 2.0, (), False)])
    ctx = {"trace": {"t0": 0.0, "t1": 2.0}, "capture": _Capture()}
    assert scopes.of(ctx) is None
    assert spec.metric_reader("kv_move_share_pct.chat")(ctx) is None


# -- the readers of the program's spans -----------------------------------------

class _Capture:
    sync = [10.0, 20.0]

    def path(self):
        return SMALL


def _ctx(rows):
    held = tracing.HostSpans()
    for row in rows:
        held.add(*row)
    return {"spans": held, "capture": _Capture(), "t0": 5.0, "t1": 25.0}


def test_serve_host_is_the_step_less_the_fetch():
    ctx = _ctx([("serve.step", 11.0, 0.120),
                ("profile.serving.fetch", 11.01, 0.090),
                ("serve.post", 11.1, 0.020), ("serve.step", 12.0, 0.130),
                ("profile.serving.fetch", 12.01, 0.090),
                ("serve.post", 12.1, 0.030),
                ("serve.step", 21.0, 0.500)])  # after the traced stretch
    assert spec.metric_reader("serve_host_ms_per_step.chat")(ctx) == \
        pytest.approx(35.0)
    assert spec.metric_reader("serve_post_ms_per_step.doc")(ctx) == \
        pytest.approx(25.0)


def test_tails_are_over_the_window_not_the_traced_stretch():
    rows = [("serve.queue_wait", 6.0 + i, 0.001 * (i + 1)) for i in range(18)]
    rows += [("serve.queue_wait", 2.0, 9.0), ("serve.first_token", 7.0, 0.2)]
    ctx = _ctx(rows)
    assert spec.metric_reader("queue_wait_p95_ms.chat")(ctx) == \
        pytest.approx(18.0)
    assert spec.metric_reader("first_token_p95_ms.chat")(ctx) == \
        pytest.approx(200.0)


@pytest.mark.parametrize("name,witness", [
    ("compiles_in_window.train", "train.feed_wait"),
    ("compiles_in_window.chat", "serve.step"),
    ("compiles_in_window.doc", "serve.step")])
def test_compiles_need_a_witness_of_the_listener(name, witness):
    read = spec.metric_reader(name)
    assert read(_ctx([("train_step", 11.0, 0.001)])) is None
    assert read(_ctx([(witness, 11.0, 0.001)])) == 0.0
    assert read(_ctx([(witness, 11.0, 0.001), ("compile.backend", 4.0, 2.0),
                      ("compile.backend", 12.0, 0.5)])) == 1.0


NEW_IN_PR_25 = ("serve_host_ms_per_step", "serve_post_ms_per_step",
                "queue_wait_p95_ms", "first_token_p95_ms",
                "feed_wait_ms_per_step", "kv_move_share_pct",
                "unscoped_share_pct",
                "attn_block_share_pct", "optimizer_share_pct",
                "attn_short_roofline.dp4", "compiles_in_window")


@pytest.mark.parametrize("name", [
    m["name"] for m in spec.benchmark()["per_layer"]
    if m["name"].startswith(NEW_IN_PR_25)])
def test_no_reader_raises_on_a_program_without_spans_or_scopes(name):
    """The parent's program emits none of the new spans, and an untraced
    or CPU run has no scopes: a reader of this PR then finds nothing."""
    ctx = _ctx([("train_step", 11.0, 0.001),
                ("profile.serving.dispatch", 11.0, 0.001),
                ("profile.serving.fetch", 11.0, 0.090)])
    ctx.update(trace=None)
    assert spec.metric_reader(name)(ctx) is None
