"""Readings the limits and rates are set from, many runs in one process.

    python3 bench/calibrate.py --workload flat-deep256-bulk \
        --seeds 101,102,103 [--control-seeds 201,202,203] [--seconds 3]
    python3 bench/calibrate.py --workload flat-deep256-online --seeds 7 \
        --rates 650,750,850 --seconds 8 [--set-rate]

For each ``--seeds`` seed it runs the cell as ``run.py`` does and prints the
compared numbers; for each ``--control-seeds`` seed it runs the control
(``faults.control``: the exact reference one precision step below the
configuration's rows, in the program's place) through the same harness.
``--rates`` instead sweeps an open-loop cell's arrival rate on the first
seed and prints the latency quantiles and the completed rate at each, to
find the knee.  Sharing one process keeps compiled programs between runs.
Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import faults  # noqa: E402
import run  # noqa: E402


def once(workload, seed, seconds, *, fault=None, traffic=None):
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds)], fault=fault,
                      overrides={"traffic": traffic} if traffic else None)
    lines = buf.getvalue().strip().splitlines()
    info = [ln for ln in lines if ln.startswith("bench:")]
    res = json.loads(lines[-1]) if rc == 0 and lines else None
    return rc, res, info


def _field(info, key):
    for line in info:
        m = re.search(rf"\b{key}=([0-9.]+)", line)
        if m:
            return float(m.group(1))
    return None


def sustained(info, seconds) -> bool:
    """The queue did not grow: the window's last answer came within three
    engine steps of the window's end."""
    window_s = _field(info, "window_s")
    step_ms = _field([ln for ln in info if ln.startswith("bench: step_ms")], "p50")
    return (window_s is not None and step_ms is not None
            and window_s - seconds <= 3 * step_ms / 1e3)


def set_rate(workload, rate):
    cell = {w["name"]: w for w in json.load(open(
        os.path.join(run.ROOT, "BENCHMARK.json")))["workloads"]}[workload]
    path = os.path.join(run.BENCH_DIR, "traffic", cell["traffic"] + ".json")
    mix = json.load(open(path))
    mix["rate_rps"] = float(rate)
    with open(path, "w") as f:
        json.dump(mix, f, indent=2)
        f.write("\n")
    print(f"calib knee: rate_rps set to {rate} in {path}", flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--rates", default="")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--set-rate", action="store_true",
                    help="after a sweep, write 0.8 x the knee into the "
                         "cell's traffic file")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    if args.rates:
        knee = None
        for rate in [float(r) for r in args.rates.split(",")]:
            rc, res, info = once(args.workload, seeds[0], args.seconds,
                                 traffic={"rate_rps": rate})
            print("calib sweep", json.dumps({"rate_rps": rate, "rc": rc,
                                             "result": res, "info": info}),
                  flush=True)
            if rc == 0 and sustained(info, args.seconds):
                knee = rate
        if args.set_rate and knee is not None:
            set_rate(args.workload, round(0.8 * knee))
        return 0
    runs = [("program", s, None) for s in seeds] + [
        ("control", int(s), faults.control)
        for s in args.control_seeds.split(",") if s]
    for kind, seed, fault in runs:
        rc, res, info = once(args.workload, seed, args.seconds, fault=fault)
        print("calib", kind, json.dumps({
            "seed": seed, "rc": rc, "correct": res and res["correct"],
            "checks": res and {k: v["value"] for k, v in res["checks"].items()},
            "metrics": res and {k: v["value"] for k, v in res["metrics"].items()},
            "info": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
