"""Files, devices, peaks and the result line shared by every kind of cell."""

from __future__ import annotations

import json
import math
import sys
from pathlib import Path
from typing import Any, Dict, List, Tuple

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
# Fixed, inside the checkout: the path is part of the cache key.
COMPILE_CACHE = ROOT / ".jax_cache"
OUT = ROOT / "bench_out"


class BenchError(RuntimeError):
    """A run that cannot produce a result (no chip, bad cell, ...)."""


def load_json(path: Path) -> Dict[str, Any]:
    with open(path) as fh:
        return json.load(fh)


def benchmark() -> Dict[str, Any]:
    path = ROOT / "BENCHMARK.json"
    if not path.exists():
        raise BenchError(f"no BENCHMARK.json at {ROOT}")
    return load_json(path)


def workload(name: str) -> Dict[str, Any]:
    path = BENCH / "workloads" / f"{name}.json"
    if not path.exists():
        raise BenchError(f"no workload file {path}")
    w = load_json(path)
    if w.get("name") != name:
        raise BenchError(f"{path} names itself {w.get('name')!r}")
    return w


def config(name: str) -> Dict[str, Any]:
    path = BENCH / "configs" / f"{name}.json"
    if not path.exists():
        raise BenchError(f"no config file {path}")
    return load_json(path)


def peaks(device_kind: str) -> Dict[str, float]:
    table = load_json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise BenchError(f"device kind {device_kind!r} is not in bench/peaks.json")
    return table[device_kind]


def enable_compile_cache() -> None:
    import jax

    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def require_chips(n: int) -> Dict[str, Any]:
    """The device record for the result line; raises without n TPU chips."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < n:
        raise BenchError(f"cell needs {n} chips, JAX found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": n}


def memory_peak_bytes(n: int) -> int:
    import jax

    peaks_ = []
    for d in jax.devices()[:n]:
        stats = d.memory_stats() or {}
        peaks_.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks_) if peaks_ else 0


def seed_key(seed: int):
    """A PRNG key from any non-negative seed, 64 bits of it kept."""
    import jax

    if seed < 0:
        raise BenchError("seed must be >= 0")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def finite(x: float) -> bool:
    return isinstance(x, (int, float)) and math.isfinite(x)


def emit(result: Dict[str, Any], checks: List[Tuple[str, float, float]]) -> None:
    """Compared numbers last on stderr, then the one JSON line on stdout."""
    for name, value, limit in checks:
        print(f"check {name}: {value!r} limit {limit!r}", file=sys.stderr)
    result = dict(result)
    result["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def metric(value: float, unit: str) -> Dict[str, Any]:
    return {"value": value, "unit": unit}


def metrics_for(cell: str, table: str) -> List[Dict[str, Any]]:
    """The metrics of one table of BENCHMARK.json that this cell reports."""
    out = []
    for m in benchmark()[table]:
        cells = m.get("workloads")
        if cells is None or cell in cells:
            out.append(m)
    return out


def out_dir(cell: str) -> Path:
    d = OUT / cell
    d.mkdir(parents=True, exist_ok=True)
    return d
