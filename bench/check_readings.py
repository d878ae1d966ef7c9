"""Readings that the limits of a cell's check are set from, on the chip at
the cell's own size: the program's sound runs, the control, and each
planted fault, over many seeds in one process (set-up and compiles are
paid once).

    python bench/check_readings.py --workload <cell> --seconds 2 --seeds 1 2 3 \
        [--control] [--fault half_batch] [--dump norms.jsonl]

Prints one JSON line per seed: {"seed", "what", "checks": {name: value}}.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import io  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="bench/check_readings.py")
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, default=2.0)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--control", action="store_true")
    p.add_argument("--fault", default=None)
    p.add_argument("--dump", default=None, help="append every seed's norms, both sides, to this JSONL file")
    ns = p.parse_args(argv)

    from harness import cell as cellmod
    from harness import common, control, faults, train

    common.enable_compile_cache()
    checks_fn = train.train_checks

    def dumping(got, want, limits, names=None):
        with open(ns.dump, "a") as fh:
            fh.write(json.dumps({"seed": seed, "got": got, "want": want, "names": names}) + "\n")
        return checks_fn(got, want, limits, names)

    if ns.dump:
        train.train_checks = control.train_checks = dumping
    for seed in ns.seeds:
        cell = cellmod.load(ns.workload, seed, ns.seconds, False, time.perf_counter())
        device = common.require_chips(cell.chips)
        if cell.traffic["kind"] != "train":
            raise common.BenchError(f"{ns.workload}: readings are for train cells")
        # every number is read; `correct` is judged by the cell's own limits
        limits = dict(cell.workload["limits"])
        cell.workload["limits"] = {n: limits.get(n, math.inf) for n in train.NUMBERS}
        t0 = time.perf_counter()
        if ns.control:
            checks = {n: v for n, v, _ in control.train_control(cell)}
            what = "control"
        else:
            out = io.StringIO()
            with contextlib.redirect_stdout(out):
                train.run(cell, device, dict(faults.TRAIN_FAULTS[ns.fault]) if ns.fault else {})
            result = json.loads(out.getvalue().strip().splitlines()[-1])
            checks = {n: c["value"] for n, c in result["checks"].items()}
            checks["correct"] = result["failed"] == 0 and all(checks[n] <= v for n, v in limits.items())
            checks["metrics"] = result["metrics"]
            checks["memory_peak_bytes"] = result["device"]["memory_peak_bytes"]
            what = ns.fault or "program"
        print(json.dumps({"seed": seed, "what": what, "seconds": time.perf_counter() - t0,
                          "checks": checks}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
