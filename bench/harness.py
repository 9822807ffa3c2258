"""What every cell shares: finding its files by name, the chip check, the
compile cache, the set-up clock, the traced window, the metric readers and
the result line.

A cell names a configuration and a traffic mix in ``BENCHMARK.json``.  The
configuration is ``configs/<config>.json`` with an optional plain reference
``configs/<config>.py`` beside it; the mix is ``traffic/<traffic>.json``,
whose ``driver`` names ``drivers/<driver>.py``; the limits of the numbers
that decide ``correct`` are ``limits/<cell>.json``; a per-layer metric is
read by ``metrics/<metric>.py``.  Nothing here names a cell, a mix or a metric.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path

from bench.trace import WINDOW

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"  # fixed: the path is part of the cache key
TRACE_DIR = ROOT / ".bench_trace"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def process_start() -> float:
    """When this process started, on the ``time.clock_gettime(CLOCK_BOOTTIME)``
    clock (Linux: field 22 of /proc/self/stat, in clock ticks since boot)."""
    with open("/proc/self/stat") as f:
        fields = f.read().rsplit(")", 1)[1].split()
    return int(fields[19]) / os.sysconf("SC_CLK_TCK")


def since_start() -> float:
    return time.clock_gettime(time.CLOCK_BOOTTIME) - process_start()


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""

    name: str
    chips: int
    config: dict  # the configuration file's contents
    config_path: Path
    traffic: dict  # the mix file's contents
    end_to_end: list  # metric entries this cell reports with --trace 0
    per_layer: list  # metric entries this cell reports with --trace 1
    limits: dict  # limits/<cell>.json: each number compared, and its limit
    bench_dir: Path  # where its configs, traffic, limits, drivers, metrics lie

    def reference(self):
        """The configuration's plain reference module, beside its file."""
        return load_module(self.config_path.with_suffix(".py"),
                           f"bench_ref_{self.config_path.stem}")


def _reports(metric: dict, cell: str, e2e_names: set) -> bool:
    """A per-layer metric with ``workloads`` is read in those cells, one
    without in every cell that reports the metric it moves."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric["moves"] in e2e_names


def load_cell(name: str, bench_file: Path = ROOT / "BENCHMARK.json") -> Cell:
    bench = load_json(bench_file)
    root = bench_file.parent
    wl = {w["name"]: w for w in bench["workloads"]}
    if name not in wl:
        raise KeyError(f"no workload {name!r} in {bench_file}")
    w = wl[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _reports(m, name, names)]
    config_path = root / conf["file"]
    bench_dir = config_path.parent.parent
    return Cell(name=name, chips=w["chips"], config=load_json(config_path),
                config_path=config_path,
                traffic=load_json(bench_dir / "traffic" / f"{w['traffic']}.json"),
                end_to_end=e2e, per_layer=per_layer,
                limits=load_json(bench_dir / "limits" / f"{name}.json")["limits"],
                bench_dir=bench_dir)


def check_chips(chips: int):
    """The devices of the cell, or NoChip."""
    import jax

    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise NoChip("JAX found no accelerator")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def use_compile_cache() -> None:
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    # cache every program, also those that compile in under a second, so a
    # run after the first compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileStats:
    """Backend compile seconds and persistent-cache hits and misses, from
    JAX's monitoring events (as chip_smoke.py counts them)."""

    def __init__(self):
        from jax import monitoring

        self.seconds, self.hits, self.misses = 0.0, 0, 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> tuple:
        return self.seconds, self.hits, self.misses


@dataclass
class Run:
    """One run of one cell: its arguments, and what the driver leaves for
    the metric readers in ``data``."""

    cell: Cell
    seed: int
    seconds: float
    trace: bool
    rehearse: bool = False
    devices: list = field(default_factory=list)
    data: dict = field(default_factory=dict)

    def say(self, *a) -> None:
        print(*a, file=sys.stderr, flush=True)


class Profile:
    """The profiler over part or all of a window, when ``run.trace``: from
    ``start`` to ``stop`` under a ``bench.window`` annotation; the trace's
    summary lands in ``run.data["trace"]``."""

    def __init__(self, run: Run):
        self.run, self.on = run, False

    def start(self) -> None:
        import jax

        if not self.run.trace:
            return
        self.dir = TRACE_DIR / self.run.cell.name
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self.window = jax.profiler.TraceAnnotation(WINDOW)
        self.window.__enter__()
        self.on = True

    def stop(self) -> None:
        import jax

        from bench import trace

        if not self.on:
            return
        self.on = False
        self.window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        try:
            self.run.data["trace"] = trace.summarize(
                trace.read(trace.find_xplane(str(self.dir))))
        except ValueError as e:
            if not self.run.rehearse:
                raise
            self.run.say(f"[trace] rehearsal, no device metric: {e}")
        shutil.rmtree(self.dir, ignore_errors=True)


@contextlib.contextmanager
def traced_window(run: Run):
    """The whole measured window under the profiler, when ``run.trace``."""
    prof = Profile(run)
    prof.start()
    try:
        yield
    finally:
        prof.stop()


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest of the cell's chips."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return max(peaks) if peaks else 0


def memory_in_use(devices) -> int:
    """Bytes in use now on the fullest of the cell's chips."""
    return max((d.memory_stats() or {}).get("bytes_in_use", 0) for d in devices)


def read_per_layer(run: Run) -> dict:
    """Each per-layer metric of the cell, from ``metrics/<name>.py``; a
    reader that finds nothing to read returns None and is left out."""
    out = {}
    for m in run.cell.per_layer:
        mod = load_module(run.cell.bench_dir / "metrics" / f"{m['name']}.py",
                          "bench_metric_" + m["name"].replace(".", "_"))
        value = mod.read(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def result_line(run: Run, res: dict) -> dict:
    """The contract's last line: correct, attempted, failed, metrics, device,
    breakdown (traced runs) and, last, the numbers compared with limits."""
    import jax

    devs = run.devices or jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs), "memory_peak_bytes": res["memory_peak_bytes"]}
    if run.trace:
        metrics = read_per_layer(run)
        tr = run.data.get("trace")
        if tr is not None:
            dev["busy_s"], dev["window_s"] = tr.busy_s, tr.window_s
    else:
        names = {m["name"]: m["unit"] for m in run.cell.end_to_end}
        metrics = {k: {"value": v, "unit": names[k]}
                   for k, v in res["end_to_end"].items() if k in names}
    line = {"correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"], "metrics": metrics, "device": dev}
    if run.trace and res.get("breakdown"):
        line["breakdown"] = res["breakdown"]
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in res["checks"].items()}
    return line


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             rehearse: bool = False) -> dict:
    """Set up, measure and check one run of ``cell``; its result line."""
    run = Run(cell, seed, seconds, trace, rehearse=rehearse)
    if not rehearse:
        run.devices = check_chips(cell.chips)
        use_compile_cache()
    stats = CompileStats()
    driver = load_module(cell.bench_dir / "drivers" / f"{cell.traffic['driver']}.py",
                         f"bench_driver_{cell.traffic['driver']}")
    return result_line(run, driver.run(run, stats))
