"""Benchmark orchestrator — one entry per paper table/figure of monitor
overhead.  Prints ``name,us_per_call,derived`` CSV rows.

    PYTHONPATH=src python -m benchmarks.run            # quick protocol
    PYTHONPATH=src REPRO_BENCH_FULL=1 python -m benchmarks.run   # 51 reps

Rows:
  overhead_case1/* : paper Table 2 col 1 (Fig 4a) — alpha/beta per instrumenter
  overhead_case2/* : paper Table 2 col 2 (Fig 4b)
  event_buffer/*   : beyond-paper buffer-strategy cost (ns/event -> us)
  beta_inproc/*    : in-process per-call beta per instrumenter
  train_loop/*     : monitoring overhead around a jit train step
"""

from __future__ import annotations

import os
from typing import List, Tuple

Row = Tuple[str, float, str]


def _rows_overhead(full: bool) -> List[Row]:
    from .overhead_case1 import run as run_case

    rows: List[Row] = []
    repeats = 51 if full else 5
    ns1 = [10_000, 200_000, 1_000_000] if not full else [10_000, 100_000, 400_000, 1_000_000]
    ns2 = [10_000, 50_000, 200_000] if not full else [10_000, 50_000, 200_000, 500_000]
    for case, ns in (("case1", ns1), ("case2", ns2)):
        results = run_case(ns, repeats, case=case)
        for r in results:
            rows.append(
                (
                    f"overhead_{case}/{r.instrumenter}",
                    r.beta * 1e6,
                    f"alpha_s={r.alpha:.3f}",
                )
            )
    return rows


def _rows_event_throughput() -> List[Row]:
    from .event_throughput import bench_buffers, bench_instrumenter_beta

    rows: List[Row] = []
    for name, ns_per_ev in bench_buffers(n_events=100_000, repeats=3).items():
        rows.append((f"event_buffer/{name}", ns_per_ev / 1e3, "per-event-append"))
    for name, beta_us in bench_instrumenter_beta(repeats=3).items():
        rows.append((f"beta_inproc/{name}", beta_us, "case2-in-process"))
    return rows


def _rows_train_overhead() -> List[Row]:
    from .train_overhead import run_loop

    rows: List[Row] = []
    base = None
    for inst in ["off", "profile", "monitoring"]:
        r = run_loop(inst, steps=20, repeats=3)
        if inst == "off":
            base = r["per_step_ms"]
        pct = (r["per_step_ms"] / base - 1) * 100 if base else 0.0
        rows.append((f"train_loop/{inst}", r["per_step_ms"] * 1e3, f"overhead_pct={pct:.1f}"))
    return rows


def main() -> None:
    full = os.environ.get("REPRO_BENCH_FULL") == "1"
    rows: List[Row] = []
    rows += _rows_overhead(full)
    rows += _rows_event_throughput()
    rows += _rows_train_overhead()
    print()
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.4f},{derived}")


if __name__ == "__main__":
    main()
