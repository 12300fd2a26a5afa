"""Shared machinery of the chip benchmark.

Finds a cell's pieces by the names in ``BENCHMARK.json`` (its configuration
file, its traffic file, its limits file, its driver and the readers of its
per-layer metrics), derives every random stream from ``--seed``, holds the
device facts (platform, kind, count, peak memory, peaks), the persistent
compilation cache, the checks that decide ``correct``, and the profiler
window that ``--trace 1`` records.
"""

from __future__ import annotations

import dataclasses
import glob
import importlib.util
import json
import math
import os
import tempfile
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_json(path: Path) -> Any:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def manifest() -> Dict[str, Any]:
    return load_json(ROOT / "BENCHMARK.json")


@dataclasses.dataclass
class Cell:
    """One workload of the manifest with its files read."""

    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, float]


def find_cell(name: str, bench: Optional[Dict[str, Any]] = None) -> Cell:
    bench = bench or manifest()
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r}; known: {sorted(work)}")
    w = work[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    limits = load_json(HERE / "limits" / f"{name}.json")
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=load_json(ROOT / conf["file"]),
        traffic=load_json(HERE / "traffic" / f"{w['traffic']}.json"),
        limits={k: float(v["limit"]) for k, v in limits["checks"].items()},
    )


def load_module(path: Path):
    """Import a file of the benchmark by its path (names may hold dots)."""
    spec = importlib.util.spec_from_file_location(
        "benchchip_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver(cell: Cell):
    return load_module(HERE / "drivers" / f"{cell.config['driver']}.py")


def reader(metric: str) -> Callable[[Any], Optional[float]]:
    return load_module(HERE / "layer_metrics" / f"{metric}.py").read


# ---------------------------------------------------------------------------
# seeds
# ---------------------------------------------------------------------------


def rng(seed: int, *tags: int) -> np.random.Generator:
    """An independent numpy stream for ``(seed, *tags)``; any whole seed."""
    return np.random.default_rng([int(x) % 2**64 for x in (seed, *tags)])


def jax_key(seed: int, *tags: int):
    import jax

    return jax.random.key(int(rng(seed, *tags, 7).integers(2**31 - 1)))


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------


def enable_compile_cache() -> str:
    """JAX's persistent cache: where ``JAX_COMPILATION_CACHE_DIR`` says,
    otherwise ``.jax_cache/`` in the checkout (a fixed path, so that the
    next run of a cell finds every program this one compiled)."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def tpu_devices(chips: int) -> list:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs


def memory_peak_bytes(devs) -> Optional[int]:
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def peaks(device_kind: str) -> Dict[str, float]:
    table = load_json(HERE / "peaks.json")["kinds"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json; known: {sorted(table)}")
    return {k: float(v) for k, v in table[device_kind].items()}


# ---------------------------------------------------------------------------
# checks
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Check:
    """A number compared with its limit; it passes at or below the limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


def max_scaled_error(out, ref) -> float:
    """max |out - ref| over max |ref|; inf where out is not finite."""
    out = np.asarray(out, np.float64)
    ref = np.asarray(ref, np.float64)
    if out.shape != ref.shape or not np.all(np.isfinite(out)):
        return math.inf
    return float(np.abs(out - ref).max() / max(np.abs(ref).max(), 1e-30))


# ---------------------------------------------------------------------------
# the run's outcome, handed from a driver to run.py and the readers
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Outcome:
    attempted: int
    failed: int
    e2e: Dict[str, float]
    checks: List[Check]
    memory_peak_bytes: Optional[int]
    # inputs of the per-layer readers: host-clock stats, event logs, the
    # reduced trace, operation and byte counts
    layer: Dict[str, Any] = dataclasses.field(default_factory=dict)
    notes: List[str] = dataclasses.field(default_factory=list)


# ---------------------------------------------------------------------------
# the profiler window
# ---------------------------------------------------------------------------


class Profile:
    """``jax.profiler`` around the measured window when ``enabled``.

    ``span(name)`` annotates a call into a layer from the benchmark's own
    files; with the profiler off it costs a no-op TraceMe.  After ``stop``,
    ``summary`` holds the reduced trace (``trace_reduce.TraceSummary``).
    """

    WINDOW = "bench.window"

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.summary = None
        self._tmp = None

    @staticmethod
    def span(name: str):
        import jax

        return jax.profiler.TraceAnnotation(name)

    def start(self) -> None:
        if not self.enabled:
            return
        import jax

        self._tmp = tempfile.TemporaryDirectory(prefix="benchchip-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self._tmp.name, profiler_options=opts)

    def stop(self) -> None:
        if not self.enabled:
            return
        import jax

        from trace_reduce import reduce_file

        jax.profiler.stop_trace()
        try:
            (path,) = glob.glob(os.path.join(self._tmp.name, "**",
                                             "*.xplane.pb"), recursive=True)
            self.summary = reduce_file(path, window=self.WINDOW)
        finally:
            self._tmp.cleanup()
