"""Chip benchmark: one cell of BENCHMARK.json per run.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell's files are found by name: bench/workloads/<name>.json names its
configuration (bench/configs/) and traffic mix (bench/traffic/), whose
"kind" picks the module in bench/harness/ that runs it. Every metric is read by
bench/metrics/<metric>.py. The last line of standard output is the result
as one JSON object; a run without the TPU chips the cell asks for exits
with an error and prints no result.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="bench/run.py")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ns = p.parse_args(argv)

    from harness import cell as cellmod
    from harness import common

    try:
        cell = cellmod.load(ns.workload, ns.seed, ns.seconds, bool(ns.trace), T0)
        common.enable_compile_cache()
        device = common.require_chips(cell.chips)
        kind = importlib.import_module(f"harness.{cell.traffic['kind']}")
        kind.run(cell, device, hooks={})
    except common.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
