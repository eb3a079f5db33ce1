"""Shared harness pieces of the chip benchmark: finding a cell's files by
name, the device check, host spans and counters, and the result line.

Everything that belongs to one configuration, traffic mix, per-layer metric
or cell lives in a file of its own under this directory, found by the name
that `BENCHMARK.json` gives it:

    configs/<config>.json          sizes, as run, with the source's keys
    configs/<config>_ref.py        plain float32 reference of that model
    configs/<config>_flops.py      its operations and bytes, from shapes
    traffic/<traffic>.json         parameters of the mix; "kind" names the
                                   runner, runners/<kind>.py
    checks/<workload>.json         the limit of each number that decides
                                   `correct`, with the readings behind it
    layer_metrics/<metric>.py      read(ctx) -> value or None
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import sys
import threading
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Tuple

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
BENCHMARK_JSON = ROOT / "BENCHMARK.json"
TRACE_DIR = HERE / "traces"            # git-ignored
COMPILE_CACHE_DIR = ROOT / ".jax_cache"  # git-ignored, fixed path


class BenchError(RuntimeError):
    """A run that cannot produce a result (no chip, missing file, ...)."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# files, by name
# ---------------------------------------------------------------------------

def load_json(path: Path) -> Any:
    if not path.is_file():
        raise BenchError(f"missing file {path.relative_to(ROOT)}")
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: Optional[str] = None):
    if not path.is_file():
        raise BenchError(f"missing file {path.relative_to(ROOT)}")
    name = name or "chipbench_" + path.stem.replace("-", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark_spec() -> Dict[str, Any]:
    return load_json(BENCHMARK_JSON)


def find_cell(spec: Dict[str, Any], workload: str) -> Dict[str, Any]:
    for cell in spec["workloads"]:
        if cell["name"] == workload:
            return cell
    raise BenchError(f"no workload named {workload!r} in BENCHMARK.json")


def config_file(spec: Dict[str, Any], name: str) -> Path:
    for c in spec["configs"]:
        if c["name"] == name:
            return ROOT / c["file"]
    raise BenchError(f"no configuration named {name!r} in BENCHMARK.json")


def load_config(spec: Dict[str, Any], name: str) -> Dict[str, Any]:
    return load_json(config_file(spec, name))


def load_reference(spec: Dict[str, Any], name: str):
    path = config_file(spec, name)
    return load_module(path.with_name(path.stem + "_ref.py"))


def load_flops(spec: Dict[str, Any], name: str):
    """The counts of configuration `name`: `configs/<config>_flops.py`."""
    path = config_file(spec, name)
    return load_module(path.with_name(path.stem + "_flops.py"))


def load_traffic(name: str) -> Dict[str, Any]:
    return load_json(HERE / "traffic" / f"{name}.json")


def load_checks(workload: str) -> Dict[str, Any]:
    return load_json(HERE / "checks" / f"{workload}.json")


def load_runner(kind: str):
    return load_module(HERE / "runners" / f"{kind}.py")


def metrics_for(spec: Dict[str, Any], workload: str, section: str
                ) -> List[Dict[str, Any]]:
    """The metrics of `section` ("end_to_end" or "per_layer") that this
    cell reports: those that list it, and those without a list whose
    moved metric the cell reports."""
    e2e = [m["name"] for m in metrics_for_e2e(spec, workload)]
    out = []
    for m in spec[section]:
        cells = m.get("workloads")
        if cells is not None:
            if workload in cells:
                out.append(m)
        elif section == "end_to_end" or m.get("moves") in e2e:
            out.append(m)
    return out


def metrics_for_e2e(spec: Dict[str, Any], workload: str
                    ) -> List[Dict[str, Any]]:
    return [m for m in spec["end_to_end"]
            if m.get("workloads") is None or workload in m["workloads"]]


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------

def require_chip(chips: int):
    """The JAX devices of the run; refuses anything but `chips` TPUs."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise BenchError(f"JAX found no devices: {e}") from e
    if devices[0].platform != "tpu":
        raise BenchError(f"needs a TPU, JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise BenchError(f"needs {chips} chips, JAX found {len(devices)}")
    return devices[:chips]


def use_compile_cache() -> str:
    """JAX's persistent compile cache: `JAX_COMPILATION_CACHE_DIR` where
    set, else a fixed directory inside the checkout."""
    import os

    import jax
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(COMPILE_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return d


def abstract(tree):
    """`ShapeDtypeStruct`s of a tree of arrays (numpy arrays as JAX would
    take them)."""
    import jax
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, jax.dtypes.canonicalize_dtype(x.dtype)), tree)


def footprint(jitted, *args) -> Optional[int]:
    """Device bytes that the compiler reserves for one call of `jitted` on
    abstract `args`: arguments, outputs not aliased to them, temporaries.
    Reuses the executable that the run's own calls compiled.  None for a
    callable that is not one jitted program (a test's planted fault)."""
    if not hasattr(jitted, "lower"):
        return None
    m = jitted.lower(*args).compile().memory_analysis()
    return int(m.argument_size_in_bytes + m.output_size_in_bytes
               - m.alias_size_in_bytes + m.temp_size_in_bytes)


def device_info(devices, footprints: Dict[str, Optional[int]]
                ) -> Dict[str, Any]:
    """The device as JAX reports it.  `memory_peak_bytes` is the larger of
    runtime's own peak (`memory_stats`, which has read below what a train
    step holds live) and the largest footprint of a program the window
    ran."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    log(f"memory: runtime peak {peak} B; program footprints {footprints}")
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": max(
                [peak, *(v for v in footprints.values() if v is not None)])}


def program_config(cfgj: Dict[str, Any]):
    """The program's `ModelConfig` for a configuration file, held to it:
    every key that the file maps onto a field of the program's config must
    carry the file's number."""
    import dataclasses

    from repro.configs import get_config
    prog = cfgj["program"]
    cfg = get_config(prog["arch"])
    if prog.get("reduced"):
        cfg = cfg.reduced()
    for key, path in prog["fields"].items():
        val = cfg
        for part in path.split("."):
            val = getattr(val, part)
        if val != cfgj[key]:
            raise BenchError(f"program's {path} is {val!r}, the configuration "
                             f"file's {key} is {cfgj[key]!r}")
    assert dataclasses.is_dataclass(cfg)
    return cfg


def key_from_seed(seed: int):
    """A JAX PRNG key for any seed up to 2**63: the low 31 bits seed the
    key and the rest is folded in."""
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


# ---------------------------------------------------------------------------
# host spans and counters
# ---------------------------------------------------------------------------

class Spans:
    """Named host-clock spans, kept in memory; each is also written into the
    profiler's trace as a `TraceAnnotation` when a trace is on."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.records: List[Tuple[str, float, float]] = []

    @contextlib.contextmanager
    def span(self, name: str):
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            try:
                yield
            finally:
                t1 = time.perf_counter()
                with self._lock:
                    self.records.append((name, t0, t1))

    def wrap(self, fn: Callable[..., Any], name: str) -> Callable[..., Any]:
        """`fn`, with each call recorded as a span `name`."""
        def wrapper(*args, **kw):
            with self.span(name):
                return fn(*args, **kw)
        return wrapper

    def durations(self, name: str) -> List[float]:
        return [t1 - t0 for n, t0, t1 in self.records if n == name]

    def total(self, name: str) -> float:
        return sum(self.durations(name))


class CompileCounter:
    """Counts XLA backend compiles (a persistent-cache hit is not one)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        import jax.monitoring as mon
        self.count = 0
        self._mon = mon
        mon.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == self.EVENT:
            self.count += 1


# ---------------------------------------------------------------------------
# what a runner hands back
# ---------------------------------------------------------------------------

@dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


@dataclass
class RunResult:
    metrics: Dict[str, float]                      # end-to-end, by name
    checks: List[Check]
    attempted: int
    failed: int
    device: Dict[str, Any]
    ctx: Dict[str, Any] = field(default_factory=dict)  # for layer readers
    breakdown: Optional[Dict[str, Any]] = None

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c.ok for c in self.checks)


def control_verdict(readings: Dict[str, float], limits: Dict[str, Any]
                    ) -> Dict[str, Any]:
    """A control's or planted fault's readings through the cell's own
    comparison: its numbers, and whether they would pass as `correct`."""
    checks = checks_from(readings, limits)
    return {"readings": dict(readings),
            "correct": all(c.ok for c in checks),
            "failed": [c.name for c in checks if not c.ok]}


def checks_from(readings: Dict[str, float], limits: Dict[str, Any]
                ) -> List[Check]:
    """Pairs each reading with its limit from checks/<workload>.json; a
    reading without a limit, or a limit without a reading, is an error."""
    want = set(limits["limits"])
    if set(readings) != want:
        raise BenchError(f"readings {sorted(readings)} != limits {sorted(want)}")
    return [Check(k, float(readings[k]), float(limits["limits"][k]["limit"]))
            for k in sorted(want)]


@dataclass
class RunArgs:
    workload: str
    seed: int
    seconds: float
    trace: bool
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, Any]
    reference: Any
    devices: Any
    t_start: float                      # perf_counter at process start
    spans: Spans = field(default_factory=Spans)
    # test hook: a function that edits the program object under test
    # before set-up drives it (used to plant faults); None in runs
    plant: Optional[Callable[..., Any]] = None
    # calibration only (calibrate.py): also put the fp8 control (and, for
    # training, half of each batch left out) in the program's place and
    # judge each by the cell's limits; the benchmark's runs never do
    control: bool = False
