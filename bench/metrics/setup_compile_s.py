"""setup_compile_s: seconds the program's monitor saw JAX compile before the
window (repro.core.jax_events): the sum of its ``jax.compile.trace_s``,
``lower_s`` and ``backend_s`` samples stamped before the window started.
``backend_s`` holds a persistent-cache hit's load. Host clock.

The train kind's ctx holds neither the monitor's run directory nor the
window's start on the host clock, so both are taken from the process: the
run directory is ``bench_out/<workload>/monitor`` (``harness/cell.py``), and
the window starts ``setup_s`` after ``bench/run.py``'s ``T0``. Compile
samples inside the window are counted on standard error; there should be
none. Where the monitor recorded no compile sample, it reads nothing."""

import argparse
import sys

from harness import common

SAMPLES = ("jax.compile.trace_s", "jax.compile.lower_s", "jax.compile.backend_s")


def read(ctx):
    t0 = getattr(sys.modules.get("__main__"), "T0", None)
    args = argparse.ArgumentParser(add_help=False)
    args.add_argument("--workload")
    workload = args.parse_known_args()[0].workload
    if ctx["kind"] != "train" or t0 is None or workload is None:
        return None
    path = common.OUT / workload / "monitor" / "metrics.json"
    if not path.exists():
        return None
    series = common.load_json(path).get("series", {})
    start = (t0 + ctx["setup_s"]) * 1e9
    end = start + ctx["window_s"] * 1e9
    samples = [(t, v) for name in SAMPLES for t, v in series.get(name, []) if v is not None]
    inside = sum(1 for t, _ in samples if start <= t < end)
    print(f"compile samples in the window: {inside}", file=sys.stderr)
    before = [v for t, v in samples if t < start]
    return sum(before) if before else None
