"""Finds a cell's files by the names in ``BENCHMARK.json``: the
configuration's sizes, the traffic mix's parameters, the runner of the
cell's kind, the adapter and the plain reference of the configuration's
model, and one reader a per-layer metric. A later PR adds any of these as a
new file and edits none."""
import importlib
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def benchmark():
    return load_json(ROOT, "BENCHMARK.json")


def load_like(folder, name):
    """``<folder>/<name>.json``; where the file says ``"like": <other>`` it
    holds what differs and takes every other key from that file, so that two
    cells of one job (one chip, four chips) keep their numbers in one place."""
    own = load_json(BENCH_DIR, folder, name + ".json")
    if "like" not in own:
        return own
    return {**load_like(folder, own.pop("like")), **own}


class Cell:
    """One entry of ``workloads`` with everything its name leads to."""

    def __init__(self, name, bench=None, config=None, traffic=None,
                 limits=None):
        """``bench``, ``config``, ``traffic`` and ``limits`` stand in for the
        files in the tests' rehearsal at a tiny size; a run reads the files."""
        bench = bench or benchmark()
        rows = [w for w in bench["workloads"] if w["name"] == name]
        if not rows:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
        self.bench, self.row, self.name = bench, rows[0], name
        self.chips = int(self.row["chips"])
        cfg_row = next(c for c in bench["configs"]
                       if c["name"] == self.row["config"])
        self.config = config or load_json(ROOT, cfg_row["file"])
        self.traffic = traffic or load_like("traffic", self.row["traffic"])
        self._limits = limits
        self.kind = self.traffic["kind"]

    def _reports(self, metric):
        return self.name in metric.get("workloads", [self.name])

    def end_to_end(self):
        return [m for m in self.bench["end_to_end"] if self._reports(m)]

    def per_layer(self):
        return [m for m in self.bench["per_layer"] if self._reports(m)]

    def runner(self):
        return importlib.import_module(f"perfbench.runners.{self.kind}")

    def adapter(self):
        return importlib.import_module(
            f"perfbench.adapters.{self.config['model']}")

    def reference(self):
        return importlib.import_module(
            f"perfbench.references.{self.config['model']}")

    def limits(self):
        """The limits of ``correct`` for this cell: ``{number: limit}``."""
        if self._limits is not None:
            return self._limits
        return load_like("limits", self.name)["limits"]


def metric_reader(name):
    """The ``read(ctx)`` of ``metrics/<name>.py`` (names hold dots, so the
    file is loaded by path)."""
    path = os.path.join(BENCH_DIR, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "perfbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read
