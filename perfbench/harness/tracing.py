"""Taking a profiler trace of part of the window, the program's host spans
on the same clock, and the reduction of both to what the per-layer metrics
read: device operations, executions of compiled programs, busy and idle
time, and each idle gap named by what the host was doing in it."""
import bisect
import glob
import os
import re
import shutil
import threading
import time

SYNC = "perfbench_sync"
#: an idle gap shorter than this is the space between two operations of one
#: program, not something the host could have filled
SHORT_GAP_S = 5e-6


class HostSpans:
    """Spans on ``time.perf_counter``: the program's own (through its
    ``span_hooks`` list) and the benchmark's, around calls into a layer."""

    def __init__(self):
        self.spans = []  # (name, start, seconds)
        self._lock = threading.Lock()

    def add(self, name, start, seconds):
        with self._lock:
            self.spans.append((name, start, seconds))

    def timed(self, name, fn):
        """``fn`` wrapped so that every call leaves a span ``name``."""
        def call(*args, **kwargs):
            t0 = time.perf_counter()
            try:
                return fn(*args, **kwargs)
            finally:
                self.add(name, t0, time.perf_counter() - t0)
        return call

    def total(self, names, t0, t1):
        with self._lock:
            return sum(d for n, s, d in self.spans
                       if n in names and t0 <= s < t1)

    def count(self, name, t0, t1):
        with self._lock:
            return sum(1 for n, s, _ in self.spans if n == name
                       and t0 <= s < t1)


class Capture:
    """One traced stretch of the window. ``start`` and ``stop`` each leave a
    ``perfbench_sync`` annotation whose host time is known, which ties the
    trace's clock to ``perf_counter``."""

    def __init__(self, directory):
        self.directory = directory
        self.sync = []  # perf_counter at each annotation

    def _mark(self):
        import jax
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation(SYNC):
            pass
        self.sync.append(t)

    def start(self):
        import jax
        shutil.rmtree(self.directory, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # the spans come from the hooks
        jax.profiler.start_trace(self.directory, profiler_options=options)
        self._mark()

    def stop(self):
        import jax
        self._mark()
        jax.profiler.stop_trace()

    def path(self):
        found = glob.glob(os.path.join(self.directory, "plugins", "profile",
                                       "*", "*.xplane.pb"))
        if not found:
            raise RuntimeError(f"no trace under {self.directory}")
        return found[0]


_HLO = re.compile(r"^%?([^\s=]+?)(?:\.\d+)? = \(*(\w+)\[([\d,]*)\]")
_KIND = re.compile(r"[}\])] ([a-z][\w\-]*)\(")


def label(name):
    """A short name for a device operation. The chip's trace names an
    operation by its whole HLO text (``%fusion.6 = f32[30522,768]{...}
    fusion(...)``): keep the name without its serial number, the type and
    shape of its (first) result, and its kind where the name does not say
    it: ``fusion_f32_30522_768``, ``transpose_jvp____bf16_1536_128_64_custom-call``.
    Operations alike in all three are one row of the breakdown."""
    found = _HLO.match(name)
    if not found:
        return re.sub(r"[.\-_]\d+$", "", name)[:64]
    base, dtype, dims = found.groups()
    kind = _KIND.search(name)
    tail = "_" + kind.group(1) if kind and kind.group(1) not in base else ""
    return f"{base}_{dtype}_{dims.replace(',', '_')}{tail}"[:96]


def read_planes(path):
    """``{"devices": {plane: {"ops": [...], "modules": [...]}}, "sync":
    [ns, ...]}`` from an ``.xplane.pb``; an event is ``(name, start_s,
    seconds)`` on the trace's clock. On a TPU a device is a plane
    ``/device:TPU:n`` with the lines ``XLA Ops`` and ``XLA Modules``. On the
    CPU (the tests' rehearsal) there is no device plane, and the host
    threads' events that carry an ``hlo_op`` stand in as one device."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    devices, sync, host_ops = {}, [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            found = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key:
                    found[key] = [(e.name, e.start_ns * 1e-9,
                                   e.duration_ns * 1e-9) for e in line.events]
            if found["ops"]:
                devices[plane.name] = found
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == SYNC:
                    sync.append(e.start_ns * 1e-9)
                elif not devices and "hlo_op" in dict(e.stats):
                    host_ops.append((e.name, e.start_ns * 1e-9,
                                     e.duration_ns * 1e-9,
                                     dict(e.stats).get("hlo_module", "")))
    if not devices and host_ops:
        devices["/host:CPU"] = {
            "ops": [o[:3] for o in host_ops],
            "modules": [(o[3], o[1], o[2]) for o in host_ops]}
    return {"devices": devices, "sync": sorted(sync)}


def union(intervals):
    """Sorted, merged ``(start, end)`` pairs."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def clip(intervals, t0, t1):
    return [(max(a, t0), min(b, t1)) for a, b in intervals
            if b > t0 and a < t1]


def gaps(merged, t0, t1):
    """The idle stretches of ``[t0, t1]`` around merged busy intervals."""
    out, cursor = [], t0
    for a, b in merged:
        if a > cursor:
            out.append((cursor, a))
        cursor = max(cursor, b)
    if t1 > cursor:
        out.append((cursor, t1))
    return out


def name_gaps(idle, host_spans):
    """Idle seconds by the host span that covered each gap's middle: the
    shortest such span, since spans nest. ``host_spans``: ``(name, start,
    seconds)`` on the same clock as ``idle``."""
    starts = sorted(host_spans, key=lambda s: s[1])
    keys = [s[1] for s in starts]
    longest = max((s[2] for s in starts), default=0.0)
    named = {}
    for a, b in idle:
        if b - a < SHORT_GAP_S:
            name = "between_operations"
        else:
            mid, best = (a + b) / 2, None
            i = bisect.bisect_right(keys, mid) - 1
            while i >= 0 and keys[i] >= mid - longest:
                n, s, d = starts[i]
                if s <= mid <= s + d and (best is None or d < best[1]):
                    best = (n, d)
                i -= 1
            name = best[0] if best else "host:no_span"
        named[name] = named.get(name, 0.0) + (b - a)
    return named


def reduce(planes, capture_sync, host_spans=()):
    """What the per-layer readers get. Times are seconds on the trace's
    clock; the window is between the two ``perfbench_sync`` marks.
    ``capture_sync`` are the marks' ``perf_counter`` times, by which
    ``host_spans`` are moved onto the trace's clock."""
    if len(planes["sync"]) < 2 or not planes["devices"]:
        return None
    t0, t1 = planes["sync"][0], planes["sync"][-1]
    offset = planes["sync"][0] - capture_sync[0]
    shifted = [(n, s + offset, d) for n, s, d in host_spans]
    per_device, busy = {}, []
    for plane, found in sorted(planes["devices"].items()):
        ops = [(n, s, d) for n, s, d in found["ops"] if s + d > t0 and s < t1]
        merged = union(clip([(s, s + d) for _, s, d in ops], t0, t1))
        busy.append(sum(b - a for a, b in merged))
        per_device[plane] = {
            "ops": ops, "busy": merged,
            "modules": [(n, s, d) for n, s, d in found["modules"]
                        if s >= t0 and s + d <= t1]}
    first = per_device[sorted(per_device)[0]]
    by_kind = {}
    for n, s, d in first["ops"]:
        by_kind[label(n)] = by_kind.get(label(n), 0.0) + d
    idle = name_gaps(gaps(first["busy"], t0, t1), shifted)
    top = lambda table: [[k, v] for k, v in sorted(
        table.items(), key=lambda kv: -kv[1])[:10]]
    return {"t0": t0, "t1": t1, "window_s": t1 - t0,
            "busy_s": sum(busy) / len(busy), "devices": per_device,
            "host_spans": shifted,
            "breakdown": {"device_ops": top(by_kind), "idle_gaps": top(idle)}}


def op_seconds(reduced, pattern, device=None):
    """Events and summed seconds of the operations whose name matches
    ``pattern`` on one device (the first by default)."""
    plane = device or sorted(reduced["devices"])[0]
    rx = re.compile(pattern)
    found = [d for n, _, d in reduced["devices"][plane]["ops"]
             if rx.search(n)]
    return len(found), sum(found)


def module_runs(reduced, pattern, device=None):
    """``(start, seconds)`` of each execution of the compiled programs whose
    name matches ``pattern``, whole inside the traced window."""
    plane = device or sorted(reduced["devices"])[0]
    rx = re.compile(pattern)
    return sorted((s, d) for n, s, d in reduced["devices"][plane]["modules"]
                  if rx.search(n))
