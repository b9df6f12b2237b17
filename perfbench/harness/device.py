"""The device a run is on: found or the run fails; its peaks from
``peaks.json`` by exact ``device_kind``; the compile cache at a fixed path
inside the checkout; the peak memory of the fullest chip."""
import os

from . import spec


class NoChip(SystemExit):
    pass


def place_compile_cache():
    """Where ``JAX_COMPILATION_CACHE_DIR`` says, else ``.jax_cache/`` at the
    root of the checkout: the program reads the same variable
    (``common/context.py``) and sets no other directory in code."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(spec.ROOT, ".jax_cache"))
    return os.environ["JAX_COMPILATION_CACHE_DIR"]


def require_chips(chips, allow_cpu=False):
    """``(devices, peaks)`` for the first ``chips`` TPU devices. No
    accelerator, fewer chips than asked, or a kind the table lacks: exit
    non-zero before any result is printed. ``allow_cpu`` is for the
    rehearsal in the tests only; it never reaches ``run.py``'s arguments."""
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not (allow_cpu and platform == "cpu"):
        raise NoChip(f"perfbench: JAX found no TPU (platform {platform!r})")
    if len(devices) < chips:
        raise NoChip(f"perfbench: the cell needs {chips} chips, JAX found "
                     f"{len(devices)}")
    table = spec.load_json(spec.BENCH_DIR, "peaks.json")["devices"]
    kind = devices[0].device_kind
    if kind not in table and not allow_cpu:
        raise NoChip(f"perfbench: no peaks for device kind {kind!r} in "
                     f"peaks.json")
    # the rehearsal's readers need some row to divide by; its numbers are
    # thrown away with the rehearsal
    return devices[:chips], table.get(kind) or next(iter(table.values()))


def describe(devices):
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": memory_peak(devices)}


def memory_peak(devices):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))
