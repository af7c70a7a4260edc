"""Helpers for the benchmark's CPU tests: a checkout-like root that holds a
copy of ``BENCHMARK.json`` cut down to one cell at a size a test run can
hold, the benchmark's own drivers, references and readers, and a run
driven on the CPU."""
from __future__ import annotations

import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for _p in (str(BENCH), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import run as harness  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CACHE_KEYS = ("jax_compilation_cache_dir",
              "jax_persistent_cache_min_compile_time_secs",
              "jax_persistent_cache_min_entry_size_bytes")


def small_root(tmp: Path, cell: str, traffic: dict, config: dict | None = None) -> Path:
    """A root with ``cell`` alone, its traffic replaced by ``traffic`` merged
    over the cell's own, and its configuration by ``config`` merged over the
    cell's own."""
    entry = dict(next(w for w in SPEC["workloads"] if w["name"] == cell))
    conf = dict(next(c for c in SPEC["configs"] if c["name"] == entry["config"]))
    base_t = json.loads((BENCH / "traffic" / f"{entry['traffic']}.json").read_text())
    base_c = json.loads((ROOT / conf["file"]).read_text())
    (tmp / "bench" / "traffic").mkdir(parents=True, exist_ok=True)
    (tmp / "bench" / "configs").mkdir(parents=True, exist_ok=True)
    for d in ("drivers", "metrics"):
        os.symlink(BENCH / d, tmp / "bench" / d)
    merged = {**base_t, **traffic}
    if "overrides" in traffic:
        merged["overrides"] = {**base_t["overrides"], **traffic["overrides"]}
    (tmp / "bench" / "traffic" / "small.json").write_text(json.dumps(merged))
    (tmp / "bench" / "configs" / "small.json").write_text(
        json.dumps({**base_c, **(config or {})}))
    entry.update(traffic="small", chips=1)
    spec = {**SPEC, "workloads": [entry],
            "configs": [{**conf, "file": "bench/configs/small.json"}]}
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


def cpu_run(root: Path, cell: str, seed: int = 4_000_000_123,
            seconds: float = 0.5, trace: bool = False) -> dict:
    """A whole run but for the look for chips: the CPU stands in."""
    import jax

    saved = {k: getattr(jax.config, k) for k in CACHE_KEYS}
    try:
        return harness.run(cell, seed, seconds, trace,
                           devices_for=lambda n: jax.devices()[:n], root=root,
                           log=lambda *a, **k: None)
    finally:
        from jax.experimental.compilation_cache import compilation_cache

        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
