#!/usr/bin/env python3
"""One run of one benchmark cell on the accelerator.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``. Everything the
run needs is found by name from it: the configuration file the entry's
``configs`` item points to, the traffic mix ``bench/traffic/<traffic>.json``,
the driver ``bench/drivers/<driver>.py`` that the traffic names, and, with
``--trace 1``, one reader ``bench/metrics/<metric>.py`` per per-layer
metric. Adding a cell, a configuration or a metric therefore adds files and
entries and edits none.

A run: checks that JAX sees the chips the cell asks for (never the CPU),
turns on the persistent compilation cache at a fixed path, lets the driver
make its inputs from ``--seed`` and warm up every shape (set-up), measures
``--seconds`` of work (the window), reads the device's peak memory, frees the
program's state, and compares a sample of the window's answers with the
driver's plain reference (``correct``). Earlier lines report the set-up's
compile seconds, cache hits and any compile inside the window. The numbers
compared are printed with their limits as the last lines on standard error,
and the last line on standard output is one JSON object.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Callable, Optional  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class SetupError(RuntimeError):
    """The cell cannot run here: a file is missing or the chips are absent."""


# -- the cell's files, found by name -----------------------------------------

def load_module(path: Path, name: Optional[str] = None):
    """Import one file of the benchmark by path (metric names hold dots)."""
    if not path.is_file():
        raise SetupError(f"missing file {path}")
    name = name or f"bench_{path.stem.replace('.', '_')}"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def _read_json(path: Path) -> dict:
    if not path.is_file():
        raise SetupError(f"missing file {path}")
    return json.loads(path.read_text())


@dataclasses.dataclass
class Cell:
    """Everything one cell names, read from ``BENCHMARK.json`` and the
    files it points to."""
    name: str
    root: Path
    chips: int
    config: dict
    traffic: dict
    driver_path: Path
    end_to_end: list
    per_layer: list


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec = _read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SetupError(f"no workload {name!r}; known: {sorted(cells)}")
    entry = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    if entry["config"] not in configs:
        raise SetupError(f"workload {name!r} names unknown configuration "
                         f"{entry['config']!r}")
    config = _read_json(root / configs[entry["config"]]["file"])
    traffic = _read_json(root / "bench" / "traffic" / f"{entry['traffic']}.json")
    driver_path = root / "bench" / "drivers" / f"{traffic['driver']}.py"
    if not driver_path.is_file():
        raise SetupError(f"missing driver {driver_path}")
    return Cell(
        name=name, root=root, chips=int(entry["chips"]), config=config, traffic=traffic,
        driver_path=driver_path,
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)])


# -- set-up bookkeeping -------------------------------------------------------

class CompileMeter:
    """XLA compile seconds (a persistent-cache read counts under the same
    event), compiles and persistent-cache hits since the last ``take``."""

    _COMPILE = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax

        self.seconds, self.compiles, self.hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == self._COMPILE:
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == self._HIT:
            self.hits += 1

    def take(self) -> dict:
        out = {"compile_s": self.seconds, "compiles": self.compiles,
               "cache_hits": self.hits}
        self.seconds, self.compiles, self.hits = 0.0, 0, 0
        return out


class Spans:
    """Host spans around the calls into each layer: kept in memory on the
    host clock, and written into the profiler's trace while one is taken."""

    def __init__(self):
        self.records: list[tuple[str, float, float]] = []
        self.tracing = False

    @contextlib.contextmanager
    def span(self, name: str):
        ann = contextlib.nullcontext()
        if self.tracing:
            import jax

            ann = jax.profiler.TraceAnnotation(f"bench.{name}")
        t0 = time.perf_counter()
        with ann:
            yield
        self.records.append((name, t0, time.perf_counter()))

    def total(self, name: str) -> tuple[float, int]:
        """(seconds, count) of the spans called ``name``."""
        d = [b - a for n, a, b in self.records if n == name]
        return sum(d), len(d)


def use_compile_cache() -> str:
    """JAX's persistent cache at a fixed path: ``JAX_COMPILATION_CACHE_DIR``
    when set, else ``.jax_cache`` at the root of the checkout. Every program
    is kept, however short its compile, so that a second run compiles
    nothing."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def accelerators(chips: int) -> list:
    """The first ``chips`` TPU devices; raises when JAX sees fewer."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise SetupError(
            f"needs {chips} TPU chip(s); JAX sees {len(devices)} "
            f"{devices[0].platform} device(s)")
    return devices[:chips]


# -- one run ------------------------------------------------------------------

@dataclasses.dataclass
class Check:
    """One number compared with the reference, and its limit."""
    name: str
    value: float
    limit: float
    ok: bool


@dataclasses.dataclass
class RunContext:
    """What a driver is handed, and what a metric reader reads."""
    cell: Cell
    seed: int
    devices: list
    spans: Spans
    peaks: dict = dataclasses.field(default_factory=dict)
    info: dict = dataclasses.field(default_factory=dict)
    window_s: float = 0.0
    units: int = 0
    work: float = 0.0
    device: dict = dataclasses.field(default_factory=dict)
    trace: Any = None

    @property
    def config(self) -> dict:
        return self.cell.config

    @property
    def traffic(self) -> dict:
        return self.cell.traffic

    def span(self, name: str):
        return self.spans.span(name)


def device_peaks(kind: str) -> dict:
    table = _read_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise SetupError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def measure(ctx: RunContext, driver, seconds: float) -> None:
    """The window: whole units of work until ``seconds`` have passed; the
    rate is all the work over all the time up to the last unit's end."""
    work, units = 0.0, 0
    with ctx.span("window"):
        t0 = time.perf_counter()
        while True:
            with ctx.span("unit"):
                work += driver.unit()
            units += 1
            elapsed = time.perf_counter() - t0
            if elapsed >= seconds:
                break
    ctx.window_s, ctx.units, ctx.work = elapsed, units, work


def memory_peak(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def traced(ctx: RunContext, driver, seconds: float) -> None:
    """The window under the profiler; its trace is reduced and deleted."""
    import jax

    import trace_reduce

    out = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        ctx.spans.tracing = True
        jax.profiler.start_trace(out)
        try:
            measure(ctx, driver, seconds)
        finally:
            jax.profiler.stop_trace()
            ctx.spans.tracing = False
        ctx.trace = trace_reduce.Reduction.from_dir(
            out, [d.id for d in ctx.devices])
    finally:
        shutil.rmtree(out, ignore_errors=True)


def read_per_layer(ctx: RunContext) -> dict:
    metrics = {}
    for m in ctx.cell.per_layer:
        reader = load_module(ctx.cell.root / "bench" / "metrics" / f"{m['name']}.py")
        value = reader.read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return metrics


def run(cell_name: str, seed: int, seconds: float, trace: bool,
        devices_for: Callable[[int], list] = accelerators,
        root: Path = ROOT, log=print) -> dict:
    """One run; returns the result object. ``devices_for`` is the look for
    chips, which a test replaces to drive the rest of a run on the CPU."""
    cell = load_cell(cell_name, root)
    for p in (str(ROOT / "src"), str(BENCH)):
        if p not in sys.path:
            sys.path.insert(0, p)
    cache_dir = use_compile_cache()
    devices = devices_for(cell.chips)
    dev = devices[0]
    meter = CompileMeter()
    ctx = RunContext(cell=cell, seed=seed, devices=devices, spans=Spans())
    ctx.device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(devices)}
    if dev.platform == "tpu":
        ctx.peaks = device_peaks(dev.device_kind)
    driver = load_module(cell.driver_path).Driver(ctx)
    driver.setup()
    setup_s = time.perf_counter() - T_PROCESS
    log("setup: " + json.dumps({"setup_s": setup_s, "cache_dir": cache_dir,
                                **meter.take(), **driver.setup_info()}),
        flush=True)

    if trace:
        # a cell whose device runs millions of tiny operations may trace
        # fewer seconds than it measures, so that the trace stays readable
        traced(ctx, driver, min(seconds, cell.traffic.get("trace_seconds", seconds)))
    else:
        measure(ctx, driver, seconds)
    window = meter.take()
    ctx.device["memory_peak_bytes"] = memory_peak(devices)
    log("window: " + json.dumps({"window_s": ctx.window_s, "units": ctx.units,
                                 "work": ctx.work,
                                 "compiles_in_window": window["compiles"],
                                 "compile_s_in_window": window["compile_s"]}),
        flush=True)

    if trace:
        log("trace: " + json.dumps({"window_s": ctx.trace.window_s(),
                                    "devices": ctx.trace.per_device()}), flush=True)
    driver.release()
    checks, failed = driver.check()
    correct = all(c.ok for c in checks) and not failed and ctx.units > 0
    if trace:
        metrics = read_per_layer(ctx)
        ctx.device.update(busy_s=ctx.trace.busy_s(), window_s=ctx.trace.window_s())
    else:
        rate = cell.traffic["rate_metric"]
        unit = {m["name"]: m["unit"] for m in cell.end_to_end}
        metrics = {rate: {"value": ctx.work / ctx.window_s, "unit": unit[rate]},
                   "setup_s": {"value": setup_s, "unit": unit["setup_s"]}}
    result = {"correct": bool(correct), "attempted": ctx.units,
              "failed": int(failed), "metrics": metrics, "device": ctx.device}
    if trace:
        result["breakdown"] = ctx.trace.breakdown()
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit,
                                 "ok": c.ok} for c in checks}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")
    try:
        result = run(args.workload, args.seed, args.seconds, bool(args.trace))
    except SetupError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['ok'] else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
