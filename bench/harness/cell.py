"""What every kind of cell shares: loading the cell by name, building the
program's configuration from the cell's config file, the monitor, the
metric readers and the result line."""

from __future__ import annotations

import importlib
import importlib.util
import math
import shutil
import statistics
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

from . import common

Check = Tuple[str, float, float]


@dataclass
class Cell:
    name: str
    workload: Dict[str, Any]
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    chips: int
    seed: int
    seconds: float
    trace: bool
    t0: float  # perf_counter at process start

    @property
    def model(self) -> Dict[str, Any]:
        return self.config["model"]

    def reference(self):
        return importlib.import_module(f"references.{self.config['reference']}")

    def program_config(self):
        """The program's ModelConfig for this cell, held to the widths and
        cuts of the config file's ``model`` block. How the program blocks,
        fuses or recomputes its work (scan chunk, remat, kernels) is the
        program's own choice and is not set here."""
        from repro.configs import SSMConfig, get_config

        overrides = dict(self.config.get("overrides", {}))
        if "ssm" in overrides:
            overrides["ssm"] = SSMConfig(**overrides["ssm"])
        cfg = get_config(self.config["arch"]).scaled(**overrides)
        for key, want in self.model.items():
            have = getattr(cfg, key)
            if key == "ssm":
                have = {k: getattr(have, k) for k in want}
            if key == "pattern":
                have = [list(s) for s in have]
            if have != want:
                raise common.BenchError(f"program config {key}={have!r}, config file says {want!r}")
        return cfg

    def counted_model(self, cfg) -> Dict[str, Any]:
        """The config file's shapes with the blocking the program runs them
        at (scan chunk, padded vocabulary), for the FLOP counter."""
        from repro.models.lm import padded_vocab

        m = dict(self.model, vocab_padded=padded_vocab(cfg.vocab))
        if "ssm" in m:
            m["ssm"] = dict(m["ssm"], chunk=cfg.ssm.chunk)
        return m


def load(workload: str, seed: int, seconds: float, trace: bool, t0: float) -> Cell:
    entry = {w["name"]: w for w in common.benchmark()["workloads"]}.get(workload)
    if entry is None:
        raise common.BenchError(f"workload {workload!r} is not in BENCHMARK.json")
    w = common.workload(workload)
    if w["config"] != entry["config"] or w["traffic"] != entry["traffic"]:
        raise common.BenchError(f"{workload}: workload file and BENCHMARK.json disagree")
    traffic = common.load_json(common.BENCH / "traffic" / f"{entry['traffic']}.json")
    return Cell(workload, w, common.config(entry["config"]), traffic, int(entry["chips"]),
                seed, seconds, trace, t0)


# ---------------------------------------------------------------------------
# Monitor
# ---------------------------------------------------------------------------

def start_monitor(cell: Cell):
    """The monitor as the cell's traffic file sets it; its run directory
    lies in the checkout and is emptied first."""
    import repro.core as rmon

    mon = cell.traffic["monitor"]
    run_dir = common.out_dir(cell.name) / "monitor"
    shutil.rmtree(run_dir, ignore_errors=True)
    return rmon.init(instrumenter=mon["instrumenter"], substrates=tuple(mon["substrates"]),
                     run_dir=str(run_dir), experiment=cell.name)


# ---------------------------------------------------------------------------
# Metric readers: bench/metrics/<name>.py, each with read(ctx) -> value|None
# ---------------------------------------------------------------------------

def read_metrics(cell: Cell, ctx: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    table = "per_layer" if cell.trace else "end_to_end"
    out = {}
    for m in common.metrics_for(cell.name, table):
        path = common.BENCH / "metrics" / f"{m['name']}.py"
        spec = importlib.util.spec_from_file_location(f"bench_metric_{len(out)}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        value = mod.read(ctx)
        if value is None:
            continue
        if not math.isfinite(value):
            raise common.BenchError(f"metric {m['name']} read {value!r}")
        out[m["name"]] = common.metric(value, m["unit"])
    return out


def leaf_gaps(prog: List[float], ref: List[float], counted: List[bool]) -> List[Optional[float]]:
    """Per leaf, |prog - ref| / max(ref, median ref); None for a leaf not counted."""
    med = statistics.median([r for r, c in zip(ref, counted) if c])
    return [abs(p - r) / max(r, med) if c else None for p, r, c in zip(prog, ref, counted)]


def finish(cell: Cell, ctx: Dict[str, Any], device: Dict[str, Any], checks: List[Check],
           attempted: int, failed: int, breakdown: Optional[Dict[str, Any]] = None) -> None:
    import sys

    if "ref_s" in ctx:
        print(f"reference took {ctx['ref_s']:.3f} s", file=sys.stderr)
    correct = all(common.finite(v) and v <= lim for _, v, lim in checks) and failed == 0
    result: Dict[str, Any] = {
        "correct": bool(correct),
        "attempted": attempted,
        "failed": failed,
        "metrics": read_metrics(cell, ctx),
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    common.emit(result, checks)
