#!/usr/bin/env python
"""Diff a BENCH_dco smoke run against the committed baseline.

    python scripts/bench_diff.py BENCH_dco.smoke.json \
        benchmarks/smoke_baseline.json

The CI bench smoke used to assert a handful of hand-picked inequalities;
everything else in BENCH_dco.json could silently regress.  This script
makes the whole trajectory load-bearing: every (row, metric) pair listed
in the baseline must exist in the fresh run and stay within its tolerance
band, so adding a metric to the baseline is all it takes to put it under
regression watch.

Baseline format (JSON)::

    {"rows": {"<row>": {"<metric>": {"max": 1.23}              # ceiling
                         "<metric>": {"min": 0.9},             # floor
                         "<metric>": {"ref": 100, "rtol": 0.1} # band
                        }, ...}}

Only deterministic metrics belong here (bytes/query, recall, skip rates,
wave counts); QPS and wall clock vary by runner and must stay out.  Rows
also carry a non-metric annotation (``provenance`` — see
``benchmarks/common.py``) which is never banded and is skipped here.
Exit code 1 on any violation; each failure is ONE line naming the metric
with its baseline value, the observed value, and the percent delta.
"""

import json
import sys

# Annotation keys benchmarks/common.py attaches to every row; structured
# metadata, not metrics — never compared, and ignored if a baseline
# accidentally lists them.
NON_METRIC_KEYS = ("provenance",)


def _delta(got: float, ref: float) -> str:
    if ref == 0:
        return "delta=n/a"
    return f"delta={100.0 * (got - ref) / abs(ref):+.1f}%"


def check(run_path: str, baseline_path: str) -> int:
    run = json.load(open(run_path))["rows"]
    spec = json.load(open(baseline_path))["rows"]
    failures = []
    for row, metrics in spec.items():
        if row not in run:
            failures.append(f"{row}: row missing from {run_path}")
            continue
        for metric, band in metrics.items():
            if metric in NON_METRIC_KEYS:
                continue
            if metric not in run[row]:
                failures.append(f"{row}.{metric}: metric missing")
                continue
            got = float(run[row][metric])
            if "max" in band and got > band["max"]:
                failures.append(
                    f"{row}.{metric}: baseline max={band['max']:.6g} "
                    f"observed={got:.6g} {_delta(got, band['max'])}")
            if "min" in band and got < band["min"]:
                failures.append(
                    f"{row}.{metric}: baseline min={band['min']:.6g} "
                    f"observed={got:.6g} {_delta(got, band['min'])}")
            if "ref" in band:
                rtol = band.get("rtol", 0.05)
                ref = band["ref"]
                if abs(got - ref) > rtol * abs(ref):
                    failures.append(
                        f"{row}.{metric}: baseline ref={ref:.6g} "
                        f"(rtol {rtol:.0%}) observed={got:.6g} "
                        f"{_delta(got, ref)}")
    if failures:
        print(f"bench diff: {len(failures)} regression(s) vs {baseline_path}")
        for f in failures:
            print(f"  FAIL {f}")
        return 1
    n = sum(len(m) for m in spec.values())
    print(f"bench diff: {n} metric(s) within tolerance of {baseline_path}")
    return 0


if __name__ == "__main__":
    if len(sys.argv) != 3:
        sys.exit(__doc__)
    sys.exit(check(sys.argv[1], sys.argv[2]))
