"""Tests for bench.py's harness pieces: the CPU-parity ratio probes (asked
for with --ratio, never selected automatically: without it a host with no
TPU is a non-zero exit), resumable sharding (BENCH_STATE.json round-trip, --shard selection),
baseline diffing, record validation, partial-record stashing, and the
argument parser. bench.py is a script, not a package module — loaded here
by file path."""
import importlib.util
import os

import pytest

_BENCH_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench.py")


def _load_bench():
    spec = importlib.util.spec_from_file_location("zoo_bench", _BENCH_PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


bench = _load_bench()


class TestRatioMode:
    def test_plan_covers_every_workload(self):
        assert set(bench._RATIO_PLAN) == set(bench._WORKLOADS)
        for impl_key, _value_key in bench._RATIO_PLAN.values():
            assert impl_key in bench._RATIO_IMPLS

    @pytest.mark.parametrize("name", [
        pytest.param(n, marks=pytest.mark.slow)
        if n in ("generate", "tp_decode") else n
        for n in sorted(bench._RATIO_PLAN)])
    def test_every_workload_lands_a_valid_record(self, name, ctx):
        """Under --ratio each workload produces one schema-valid ratio
        record with no accelerator at all. Impl results are
        memoized, so the parametrizations run one actual probe per impl
        key. The ``generate`` probe decodes 32 serial reference streams
        (minutes of wall time) and runs in the slow tier."""
        rec = bench._run_ratio(name)
        assert bench._validate_record(rec) == []
        assert rec["metric"] == f"{name}_cpu_ratio"
        assert rec["unit"] == "ratio"
        d = rec["detail"]
        assert d["mode"] == "cpu_ratio"
        assert d["proxy_for"] == name
        if rec["value"] is not None:  # mp ratio is None where fork isn't
            assert rec["value"] > 0

    def test_obs_ratio_honors_disabled_contract(self):
        detail = bench._ratio_memo.get("obs") or bench._ratio_obs()
        assert detail["disabled_under_1us"] is True


class TestShardAndState:
    def test_shards_partition_the_run_order(self):
        names = list(bench._WORKLOADS)
        shards = [bench._select_shard(names, (i, 3)) for i in range(3)]
        flat = [n for s in shards for n in s]
        assert sorted(flat) == sorted(names)      # disjoint and complete
        assert len(flat) == len(set(flat))
        # round-robin: the expensive head rows spread across shards
        assert names[0] in shards[0] and names[1] in shards[1]
        assert bench._select_shard(names, None) == names

    def test_state_roundtrip(self, tmp_path, monkeypatch):
        monkeypatch.setattr(bench, "_STATE_PATH",
                            str(tmp_path / "BENCH_STATE.json"))
        assert bench._load_state() == {}
        results = {"resnet50": bench._BenchResult(
            metric="resnet50_cpu_ratio", value=2.5, unit="ratio",
            mfu=None, detail={"mode": "cpu_ratio"})}
        bench._save_state(results)
        loaded = bench._load_state()
        assert set(loaded) == {"resnet50"}
        assert loaded["resnet50"]["value"] == 2.5
        assert isinstance(loaded["resnet50"], bench._BenchResult)
        bench._clear_state()
        assert bench._load_state() == {}
        bench._clear_state()  # idempotent

    def test_corrupt_state_is_ignored(self, tmp_path, monkeypatch):
        path = tmp_path / "BENCH_STATE.json"
        path.write_text("{not json")
        monkeypatch.setattr(bench, "_STATE_PATH", str(path))
        assert bench._load_state() == {}


class TestBaseline:
    def test_diff_math_and_filters(self):
        baseline = {"workloads": {
            "a": {"value": 100.0, "unit": "images/s"},
            "b": {"value": 10.0, "unit": "ratio"},
            "c": {"value": 50.0, "unit": "images/s"},
            "z": {"value": 0.0, "unit": "x"},
        }}
        results = {
            "a": bench._BenchResult(metric="a", value=110.0,
                                    unit="images/s", detail={}),
            "b": bench._BenchResult(metric="b", value=10.0,
                                    unit="records/s", detail={}),  # unit drift
            "c": bench._BenchResult(metric="c", value=None,
                                    unit="images/s", detail={}),   # no value
            "z": bench._BenchResult(metric="z", value=3.0,
                                    unit="x", detail={}),          # zero base
            "d": bench._BenchResult(metric="d", value=1.0,
                                    unit="x", detail={}),          # no base
        }
        assert bench._baseline_diff(results, baseline) == {"a": 10.0}

    def test_diff_is_null_without_reference_numbers(self):
        results = {"a": bench._BenchResult(metric="a", value=1.0,
                                           unit="x", detail={})}
        assert bench._baseline_diff(results, {}) is None
        assert bench._baseline_diff(results, {"published": {}}) is None

    def test_write_then_diff_roundtrip(self, tmp_path, monkeypatch):
        monkeypatch.setenv("BENCH_BASELINE",
                           str(tmp_path / "BASELINE.json"))
        results = {"a": bench._BenchResult(metric="a", value=200.0,
                                           unit="x", detail={})}
        doc = {"workloads": {"a": {"value": 160.0, "unit": "x"}}}
        (tmp_path / "BASELINE.json").write_text(__import__("json").dumps(doc))
        assert bench._baseline_diff(results) == {"a": 25.0}


class TestRecordSchema:
    def test_valid_record_is_clean(self):
        rec = bench._BenchResult(metric="x_cpu_ratio", value=1.5,
                                 unit="ratio", mfu=None, detail={})
        assert bench._validate_record(rec) == []
        rec["value"] = None  # null value is legal (failed sub-probe)
        assert bench._validate_record(rec) == []

    def test_junk_records_are_named(self):
        assert bench._validate_record("nope") == ["record must be a dict"]
        problems = bench._validate_record({"metric": "", "unit": 3,
                                           "value": "fast", "detail": []})
        assert len(problems) == 4

    def test_note_partial_stashes_best_so_far(self):
        saved = dict(bench._PARTIAL), dict(bench._PARTIAL["detail"])
        try:
            bench._PARTIAL.clear()
            bench._PARTIAL["detail"] = {}
            bench._note_partial(warmup_done=True)
            assert "metric" not in bench._PARTIAL
            bench._note_partial(metric="m", value=7.0, unit="u", rate=7.0)
            assert bench._PARTIAL["metric"] == "m"
            assert bench._PARTIAL["value"] == 7.0
            assert bench._PARTIAL["detail"] == {"warmup_done": True,
                                                "rate": 7.0}
        finally:
            bench._PARTIAL.clear()
            bench._PARTIAL.update(saved[0])
            bench._PARTIAL["detail"] = saved[1]


def _gate_baseline():
    return {"workloads": {
        "ncf": {"value": 9.4e6, "unit": "samples/s", "mfu": 0.0075,
                "detail": {"hbm_roofline_fraction": 0.5,
                           "embedding_fused_speedup": 1.3}},
        "widedeep": {"value": 2.9e6, "unit": "samples/s", "mfu": 0.0001,
                     "detail": {"hbm_roofline_fraction": 0.4}},
    }}


def _real_record(name, mfu, frac):
    return bench._BenchResult(
        metric=f"{name}_train_samples_per_sec", value=1e6,
        unit="samples/s", mfu=mfu,
        detail={"hbm_roofline_fraction": frac})


class TestRooflineGate:
    def test_healthy_round_passes(self):
        results = {"ncf": _real_record("ncf", 0.0074, 0.49),
                   "widedeep": _real_record("widedeep", 0.0001, 0.41)}
        assert bench._gate_check(results, _gate_baseline()) == []
        assert bench._apply_gate(results, baseline=_gate_baseline()) == []
        assert results["ncf"]["detail"]["roofline_gate_ok"] is True

    def test_synthetic_regression_fails_with_explicit_fields(self):
        """A regressed round — roofline fraction halves while samples/s
        holds — must fail the gate AND stamp the failure into the record,
        not just the exit code."""
        results = {"ncf": _real_record("ncf", 0.003, 0.2),
                   "widedeep": _real_record("widedeep", 0.00005, 0.1)}
        failures = bench._apply_gate(results, baseline=_gate_baseline())
        kinds = {f.split(":")[0] for f in failures}
        # widedeep.mfu is exempt: its 0.0001 baseline is below the noise
        # floor (gather-bound steps are judged by the hbm fraction)
        assert kinds == {"ncf.hbm_roofline_fraction", "ncf.mfu",
                         "widedeep.hbm_roofline_fraction"}
        assert results["ncf"]["detail"]["roofline_gate_ok"] is False
        assert results["ncf"]["detail"]["roofline_gate_failures"]
        assert results["widedeep"]["detail"]["roofline_gate_ok"] is False

    def test_tolerance_is_relative(self):
        results = {"ncf": _real_record("ncf", 0.0075, 0.46)}  # -8% ok
        assert bench._gate_check(results, _gate_baseline()) == []
        results = {"ncf": _real_record("ncf", 0.0075, 0.44)}  # -12% not
        assert len(bench._gate_check(results, _gate_baseline())) == 1

    def test_ratio_failed_and_unbaselined_records_are_exempt(self):
        ratio = bench._BenchResult(metric="ncf_cpu_ratio", value=2.5,
                                   unit="ratio", mfu=None,
                                   detail={"mode": "cpu_ratio"})
        failed = bench._BenchResult(metric="widedeep_failed", value=None,
                                    unit="", mfu=None,
                                    detail={"error": "boom"})
        fresh = _real_record("widedeep_sharded", 0.001, 0.01)  # no base
        results = {"ncf": ratio, "widedeep": failed,
                   "widedeep_sharded": fresh}
        assert bench._gate_check(results, _gate_baseline()) == []
        bench._apply_gate(results, baseline=_gate_baseline())
        assert "roofline_gate_ok" not in ratio["detail"]

    def test_no_gate_skips_and_stamps(self):
        results = {"ncf": _real_record("ncf", 0.001, 0.01)}  # regressed
        assert bench._apply_gate(results, no_gate=True,
                                 baseline=_gate_baseline()) == []
        assert results["ncf"]["detail"]["roofline_gate"] == "skipped"
        assert "roofline_gate_ok" not in results["ncf"]["detail"]

    def test_write_baseline_records_mfu_and_fused_speedup(
            self, tmp_path, monkeypatch):
        """--write-baseline must persist everything the gate and the
        fused-A/B diff later compare: mfu at the top level, the roofline
        fraction and embedding_fused_speedup in the tracked detail."""
        monkeypatch.setattr(bench, "__file__",
                            str(tmp_path / "bench.py"))
        results = {"ncf": bench._BenchResult(
            metric="ncf_train_samples_per_sec", value=9.4e6,
            unit="samples/s", mfu=0.0075,
            detail={"hbm_roofline_fraction": 0.5,
                    "embedding_fused_speedup": 1.3})}
        bench._write_baseline(results)
        doc = __import__("json").loads(
            (tmp_path / "BASELINE.json").read_text())
        entry = doc["workloads"]["ncf"]
        assert entry["mfu"] == 0.0075
        assert entry["detail"]["hbm_roofline_fraction"] == 0.5
        assert entry["detail"]["embedding_fused_speedup"] == 1.3
        # and the round that just wrote it gates green against it
        assert bench._gate_check(results, doc) == []

    def test_regressed_resumed_round_exits_nonzero(self, tmp_path):
        """End-to-end: a real bench.py invocation whose (resumed) round
        regressed vs BASELINE.json must exit nonzero with the gate
        verdict in the compact line; --no-gate is the escape hatch."""
        import json as _json
        import subprocess
        import sys as _sys
        baseline = tmp_path / "BASELINE.json"
        baseline.write_text(_json.dumps(_gate_baseline()))
        state = {"results": {"ncf": dict(_real_record("ncf", 0.003, 0.2))}}
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   BENCH_BASELINE=str(baseline))
        saved = None
        if os.path.exists(bench._STATE_PATH):
            saved = open(bench._STATE_PATH).read()
        # the subprocess rewrites the repo's BENCH_DETAIL.json — a tracked
        # bench artifact — so park the original for the finally block
        detail_path = os.path.join(os.path.dirname(_BENCH_PATH),
                                   "BENCH_DETAIL.json")
        saved_detail = None
        if os.path.exists(detail_path):
            saved_detail = open(detail_path).read()
        try:
            with open(bench._STATE_PATH, "w") as f:
                _json.dump(state, f)
            proc = subprocess.run(
                [_sys.executable, _BENCH_PATH, "ncf", "--resume"],
                capture_output=True, text=True, timeout=240, env=env)
            assert proc.returncode == 3, proc.stdout + proc.stderr
            final = _json.loads(proc.stdout.strip().splitlines()[-1])
            row = final["detail"]["workloads"]["ncf"]
            assert row["roofline_gate_ok"] is False

            with open(bench._STATE_PATH, "w") as f:
                _json.dump(state, f)
            proc = subprocess.run(
                [_sys.executable, _BENCH_PATH, "ncf", "--resume",
                 "--no-gate"],
                capture_output=True, text=True, timeout=240, env=env)
            assert proc.returncode == 0, proc.stdout + proc.stderr
        finally:
            if saved is not None:
                open(bench._STATE_PATH, "w").write(saved)
            else:
                bench._clear_state()
            if saved_detail is not None:
                open(detail_path, "w").write(saved_detail)
            elif os.path.exists(detail_path):
                os.remove(detail_path)


class TestArgs:
    def test_defaults(self):
        args = bench._parse_args([])
        assert args["which"] == "all" and args["one"] is None
        assert not args["ratio"] and not args["resume"]
        assert args["shard"] is None and args["budget"] is None
        assert not args["no_gate"]

    def test_flags_and_aliases(self):
        args = bench._parse_args(["--one", "input_pipeline",
                                  "--budget", "120.5"])
        assert args["one"] == "pipeline"  # alias resolved
        assert args["budget"] == 120.5
        args = bench._parse_args(["--ratio", "--resume",
                                  "--write-baseline", "--shard", "1/4",
                                  "--no-gate", "eval"])
        assert args["ratio"] and args["resume"]
        assert args["write_baseline"]
        assert args["no_gate"]
        assert args["shard"] == (1, 4)
        assert args["which"] == "eval"

    def test_bad_input_rejected(self):
        with pytest.raises(SystemExit):
            bench._parse_args(["--wat"])
        with pytest.raises(SystemExit):  # went with the automatic degrade
            bench._parse_args(["--full"])
        with pytest.raises(SystemExit):
            bench._parse_args(["--shard", "4/4"])


class TestNoTpuIsAnError:
    def test_workload_without_tpu_exits_nonzero(self, tmp_path):
        """No automatic degrade: a workload asked of a host where JAX finds
        no TPU exits non-zero with a message, runs no CPU proxy under the
        workload's name and writes no record."""
        import subprocess
        import sys as _sys
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.run([_sys.executable, _BENCH_PATH, "ncf"],
                              capture_output=True, text=True, timeout=120,
                              env=env, cwd=str(tmp_path))
        assert proc.returncode == bench._NO_TPU_RC, proc.stderr[-800:]
        assert "found no TPU" in proc.stderr
        assert "--ratio" in proc.stderr
        assert "cpu_ratio" not in proc.stdout
        assert bench._MARKER not in proc.stdout
