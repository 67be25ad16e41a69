"""Smoke test of the DADE serving path on TPU, in one process.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # the corpus-sharded paths on four chips

One chip runs two phases through ``repro.launch.serve.main``, the entry
point a user calls, at the paper's 256-d width with a corpus made from
seed 0:

  flat   1,048,576 rows x 256, int8 codes + bf16 rows, served through the
         compiled ``ivf_scan`` megakernel (Δd=128, batch 64, k=10, 8
         requests); recall@10 against ``exact_knn`` must reach 0.95.
  graph  a 16,384-node NSW graph at 256-d served through the compiled
         ``graph_scan`` megakernel; ``--verify-graph-oracle`` must find the
         served ids identical to the single-host beam oracle.

``--chips 4`` runs only the paths that exist across chips: the flat route
over a 4-device mesh at 1,048,576 rows per chip (hierarchical top-K merge),
and an 8,192-node graph with ``--graph-shards 4`` checked against the
single-host oracle.

Each phase prints one line: route, rows x dim, recall, compile time, the
phase's wall time on the host clock, and the kernel mode serve resolved.
The script exits nonzero, and prints no result line, when JAX finds no
TPU, when the ``repro`` package is not next to it, when a kernel resolved
to interpret mode, when recall is below its bar, or when an oracle check
fails.  On success the last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

DIM = 256
FLAT_ROWS_PER_CHIP = 1 << 20
# Graph nodes by chip count: the host build is O(N) Python inserts (about
# 90 s for 16,384 nodes at 256-d), and a four-chip second costs four.
GRAPH_NODES = {1: 16384, 4: 8192}
FLAT_RECALL_BAR = 0.95


Phase = tuple[str, list[str], float | None, str | None]


def phases(chips: int) -> list[Phase]:
    """(name, serve argv, recall bar, acceptance check that must pass).

    The graph walk is approximate (an ef=48 beam), so its phase has no
    recall bar: its contract is identity with the single-host oracle, and
    its recall is reported (the oracle itself reaches 0.88 on the served
    queries of the 16,384-node graph and 0.80 on the 8,192-node one, on
    the CPU)."""
    common = ["--devices", str(chips), "--dim", str(DIM), "--batch", "64",
              "--k", "10"]
    flat = (f"flat x{chips}", common + [
        "--corpus-per-device", str(FLAT_ROWS_PER_CHIP), "--quant", "int8",
        "--fused", "on", "--requests", "8"], FLAT_RECALL_BAR, None)
    graph_args = common + [
        "--index", "graph", "--corpus-per-device",
        str(GRAPH_NODES[chips] // chips),
        "--requests", "4", "--verify-graph-oracle"]
    if chips > 1:
        graph_args += ["--graph-shards", str(chips)]
    graph = (f"graph x{chips}", graph_args, None, "graph_oracle")
    return [flat, graph]


def run_phase(serve, name, argv, recall_bar, must_verify) -> list[str]:
    t0 = time.perf_counter()
    try:
        report = serve.main(argv)
    except SystemExit as e:  # serve's acceptance checks exit with a reason
        return [f"{name}: serve exited: {e}"]
    except Exception as e:  # noqa: BLE001 - report it, run the next phase
        traceback.print_exc()
        return [f"{name}: {type(e).__name__}: {e}"]
    wall_s = time.perf_counter() - t0
    rows = report["queries"]
    route = argv[argv.index("--index") + 1] if "--index" in argv else "flat"
    n = int(argv[argv.index("--corpus-per-device") + 1]) * report["devices"]
    print(f"phase {name}: route={route} rows={n}x{DIM} "
          f"recall@10={report['recall']:.4f} "
          f"compile_ms={report['compile_ms']:.0f} "
          f"wall_s_host_clock={wall_s:.1f} kernels={report['kernels']} "
          f"platform={report['platform']} devices={report['devices']} "
          f"queries={rows} verified={','.join(report['verified']) or '-'}",
          flush=True)
    problems = []
    if report["platform"] != "tpu":
        problems.append(f"{name}: served on {report['platform']}, not tpu")
    if report["kernels"] != "compiled":
        problems.append(f"{name}: kernels ran in {report['kernels']} mode")
    if recall_bar is not None and not report["recall"] >= recall_bar:
        problems.append(
            f"{name}: recall@10 {report['recall']:.4f} < {recall_bar}")
    if must_verify and must_verify not in report["verified"]:
        problems.append(f"{name}: {must_verify} check did not pass")
    return problems


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.join(here, "src"))
    try:
        from repro.launch import serve
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the serving package from "
              f"{here}/src: {e}", file=sys.stderr)
        return 2

    import jax

    enable_compile_cache()
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform={platform}); "
              f"refusing to run on another backend", file=sys.stderr)
        return 3
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, JAX sees {len(devices)}", file=sys.stderr)
        return 3

    problems = []
    for name, serve_argv, bar, must_verify in phases(args.chips):
        problems += run_phase(serve, name, serve_argv, bar, must_verify)
    if problems:
        for p in problems:
            print(f"chip_smoke: FAIL {p}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
