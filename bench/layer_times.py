"""Device time of one train cell by model layer, from one traced run.

    python bench/layer_times.py --workload <cell> --seed <n> [--seconds 25]

Runs the cell as ``bench/run.py --trace 1`` does and, before the harness
removes the trace, reduces it by the program's layer scopes
(``harness/scopes.py``) against the optimized HLO text of the step that ran
(``lower(...).compile().as_text()`` on the step's own argument shapes).
Prints the run's result line, the reduction on standard error, and last one
JSON line: the per-layer numbers, the window's busy time beside the total
operation self time, the idle time by host span, and the monitor's compile
samples before and inside the window.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

COMPILE = ("jax.compile.trace_s", "jax.compile.lower_s", "jax.compile.backend_s",
           "jax.compile.cache_load_s")


def compile_samples(path: Path, start_ns: float, end_ns: float):
    """Per compile metric: (seconds before the window, samples inside it)."""
    from harness import common

    series = common.load_json(path).get("series", {}) if path.exists() else {}
    out = {}
    for name in COMPILE:
        vals = [(t, v) for t, v in series.get(name, []) if v is not None]
        out[name] = (sum(v for t, v in vals if t < start_ns),
                     sum(1 for t, _ in vals if start_ns <= t < end_ns))
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="bench/layer_times.py")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, default=25.0)
    ns = p.parse_args(argv)

    import jax

    from harness import cell as cellmod
    from harness import common, scopes, trace, train

    cell = cellmod.load(ns.workload, ns.seed, ns.seconds, True, T0)
    common.enable_compile_cache()
    device = common.require_chips(cell.chips)
    names = scopes.layer_scopes()
    if cell.traffic["kind"] != "train" or names is None:
        raise common.BenchError(f"{ns.workload}: needs a train cell and a program with layer scopes")
    seen = {}

    def wrap_step(f):
        def step(*args):
            if "args" not in seen:
                seen["args"] = jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding), args)
            return f(*args)

        seen["step"] = f
        return step

    reduce_trace, read_metrics = trace.reduce, cellmod.read_metrics

    def reduce_both(path, *a, **k):
        hlo = seen["step"].lower(*seen["args"]).compile().as_text()
        seen["scopes"] = scopes.reduce(path, hlo, names)
        return reduce_trace(path, *a, **k)

    def keep_ctx(cell_, ctx):
        seen["ctx"] = ctx
        return read_metrics(cell_, ctx)

    trace.reduce, cellmod.read_metrics = reduce_both, keep_ctx
    try:
        train.run(cell, device, {"wrap_step": wrap_step})
    finally:
        trace.reduce, cellmod.read_metrics = reduce_trace, read_metrics
    ctx, red = seen["ctx"], seen["scopes"]
    start = (T0 + ctx["setup_s"]) * 1e9
    compiles = compile_samples(common.OUT / cell.name / "monitor" / "metrics.json",
                               start, start + ctx["window_s"] * 1e9)
    print(json.dumps(red, indent=1), file=sys.stderr)
    print(json.dumps({
        "workload": cell.name, "seed": cell.seed, "steps": ctx["steps"],
        "setup_s": ctx["setup_s"], "window_s": ctx["window_s"],
        "tokens_per_s": ctx["tokens"] / ctx["window_s"],
        "busy_s": red["busy_s"], "op_self_s": red["total_s"],
        "scope_sum_ns_equals_total": sum(red["scope_ns"].values()) == red["total_ns"],
        "not_in_hlo": red["not_in_hlo"], "other_programs": red["other_programs"],
        "per_step": scopes.per_step(red, ctx["steps"]),
        "recompute_ms": {k: 1e3 * v / ctx["steps"] for k, v in red["recompute_scope_s"].items()},
        "unscoped_ops": red["unscoped_ops"], "idle_by_span": red["idle_by_span"],
        "long_gaps": red["long_gaps"][:5],
        "setup_compile_s": sum(compiles[n][0] for n in COMPILE[:3]),
        "cache_load_s": compiles[COMPILE[3]][0],
        "compile_samples_in_window": sum(c[1] for c in compiles.values()),
    }), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
