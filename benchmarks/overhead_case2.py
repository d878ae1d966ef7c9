"""Paper Table 2 / Fig. 4b — test case 2 (function calls).

Same protocol as case 1 over the Listing-4 kernel (one Python function call
per iteration).  Paper reference: None beta=0.3us; setprofile beta=15.0us;
settrace beta=17.9us per iteration.  Claims reproduced: (1) per-call cost
dominates both instrumenters; (2) setprofile < settrace; (3) the ordering
and magnitude gap justify setprofile as the default instrumenter.

Beyond-paper rows: sampling (the paper's future-work suggestion),
sys.monitoring (PEP 669) and the adaptive PEP 669 epoch sampler quantify how
much of the per-call beta is recoverable.

Filtered-residual rows (``<inst>+filtered``) run the kernel with
``--filter=exclude:*`` — every region filtered, nothing recorded — so their
beta minus the ``none``-instrumenter baseline is the pure per-call cost of a
*filtered* verdict.  Under ``profile`` that residual is a real per-call
dict-lookup cost; under ``monitoring`` the DISABLE protocol retires filtered
locations after one hit, so the residual must be ~0.  ``--smoke`` (the
3.12+ CI job) asserts exactly that.

    PYTHONPATH=src python -m benchmarks.overhead_case2            # full fit
    PYTHONPATH=src python -m benchmarks.overhead_case2 --smoke    # CI
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List, Optional

from .overhead_case1 import run
from repro.core.overhead import measure_case

DEFAULT_NS = [10_000, 50_000, 200_000, 500_000]
SMOKE_NS = [50_000, 300_000]

_HAS_MONITORING = hasattr(sys, "monitoring")


def filtered_rows(ns: List[int], repeats: int):
    """``exclude:*`` rows: the kernel under an everything-filtered run."""
    rows = []
    insts = ["profile"] + (["monitoring"] if _HAS_MONITORING else [])
    for inst in insts:
        res = measure_case(
            "case2", inst, ns, repeats=repeats, extra_args=("--filter=exclude:*",)
        )
        res.instrumenter = f"{inst}+filtered"
        print(
            f"case2 {res.instrumenter:20s} alpha={res.alpha:7.3f} s  "
            f"beta={res.beta * 1e6:8.3f} us/iter  "
            f"medians={['%.3f' % m for m in res.medians]}"
        )
        rows.append(res)
    return rows


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--repeats", type=int, default=7, help="51 for the paper's full protocol")
    p.add_argument("--ns", type=int, nargs="*", default=None)
    p.add_argument("--smoke", action="store_true",
                   help="CI mode: fewer/smaller rows + hard asserts on the "
                        "DISABLE zero-residual claim (needs 3.12+ for the "
                        "monitoring/adaptive rows)")
    p.add_argument("--out", default="benchmarks/artifacts/overhead_case2.json")
    args = p.parse_args(argv)
    ns = args.ns or (SMOKE_NS if args.smoke else DEFAULT_NS)
    repeats = 3 if args.smoke and args.repeats == 7 else args.repeats

    if args.smoke:
        instrumenters = [None, "none", "profile"]
    else:
        instrumenters = [None, "none", "profile", "trace", "sampling"]
    if _HAS_MONITORING:
        instrumenters += ["monitoring", "adaptive"]
    else:
        print("note: monitoring/adaptive rows skipped (sys.monitoring needs 3.12+)")

    results = run(ns, repeats, instrumenters=instrumenters, case="case2")
    results += filtered_rows(ns, repeats)

    by_name = {r.instrumenter: r for r in results}
    base = by_name["none"].beta  # measurement loaded, instrumenter none
    residuals = {
        name: by_name[name].beta - base
        for name in by_name
        if name.endswith("+filtered")
    }
    doc = {
        "ns": ns,
        "repeats": repeats,
        "smoke": args.smoke,
        "rows": [r.__dict__ for r in results],
        "filtered_residual_us": {k: v * 1e6 for k, v in residuals.items()},
    }
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as fh:
        json.dump(doc, fh, indent=1)

    # the paper's headline claim, asserted
    if "profile" in by_name and "trace" in by_name:
        ok = by_name["profile"].beta < by_name["trace"].beta
        print(f"claim(setprofile beta < settrace beta): {'CONFIRMED' if ok else 'REFUTED'}")

    res_prof = residuals.get("profile+filtered")
    res_mon = residuals.get("monitoring+filtered")
    if res_prof is not None:
        print(f"filtered residual [profile]    {res_prof * 1e6:8.4f} us/iter")
    if res_mon is not None:
        print(f"filtered residual [monitoring] {res_mon * 1e6:8.4f} us/iter")
        # DISABLE claim: filtered regions cost ~0 per call under monitoring
        # (one hit per location per epoch), vs profile's real per-call
        # filtered fast path.  0.1 us absolute floor absorbs subprocess
        # timing noise in the beta fit at smoke scale.
        zero = res_mon <= max(0.3 * res_prof, 0.1e-6)
        print(f"claim(monitoring filtered residual ~0): "
              f"{'CONFIRMED' if zero else 'REFUTED'}")
        if args.smoke:
            assert zero, (
                f"monitoring filtered residual not ~0: {res_mon * 1e6:.4f} us/iter "
                f"(profile residual {res_prof * 1e6:.4f} us/iter)"
            )
    if "adaptive" in by_name and "monitoring" in by_name:
        b_ad = by_name["adaptive"].beta - base
        b_mon = by_name["monitoring"].beta - base
        print(f"beta-over-none [monitoring] {b_mon * 1e6:8.4f} us/iter, "
              f"[adaptive] {b_ad * 1e6:8.4f} us/iter")
        if args.smoke:
            # The adaptive sampler DISABLEs unsampled calls entirely, so its
            # per-call cost must undercut exhaustive monitoring clearly.
            assert b_ad <= 0.5 * b_mon + 0.1e-6, (
                f"adaptive beta not below monitoring beta: "
                f"{b_ad * 1e6:.4f} vs {b_mon * 1e6:.4f} us/iter"
            )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
