"""Run one benchmark cell once, on the chips of the machine it starts on.

    python3 bench/run.py --workload flat-deep256-bulk --seed 7 \
        --seconds 10 --trace 0

The cell, its configuration, its traffic mix and its metrics are read from
``BENCHMARK.json`` and the files it names: ``bench/configs/<config>.json``,
``bench/traffic/<traffic>.json`` and ``bench/metrics/<metric>.py``.  One
run:

  1. refuses to start (exit 3, no result) unless JAX finds TPU chips of a
     ``device_kind`` listed in ``bench/peaks.json``, as many as the cell asks
     for; exits 2 when the program (``src/repro``) is not beside it;
  2. makes the corpus and the query pool on the device from ``--seed`` and
     builds the serving engine (``engine.py``), then warms up the shapes
     this cell's traffic uses;
  3. measures ``--seconds`` of traffic (``traffic.py``) through the
     program's ``BatchScheduler``, counting the programs lowered inside the
     window;
  4. reads the peak device memory, frees the program's state, and compares
     every answer with the exact reference (``reference.py``);
  5. prints one JSON line, the last line of standard output.  With
     ``--trace 0`` its metrics are the cell's end-to-end metrics; with
     ``--trace 1`` the window runs under the profiler and the metrics are
     the cell's per-layer metrics, read from the trace.

Set-up (``setup_s``) runs from process start to the window's first request.
JAX's persistent compilation cache is kept in ``bench/.cache/jax``, inside
the checkout, whatever ``JAX_COMPILATION_CACHE_DIR`` said before.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
CACHE_DIR = os.path.join(BENCH_DIR, ".cache")


def _json(path):
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str) -> SimpleNamespace:
    """Everything one cell needs, found by the names in BENCHMARK.json."""
    bench = _json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"bench: no workload {workload!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]

    def mine(metric):
        return workload in metric.get("workloads", [workload])

    return SimpleNamespace(
        name=workload, chips=int(cell["chips"]),
        cfg=_json(os.path.join(ROOT, conf["file"])),
        traffic=_json(os.path.join(BENCH_DIR, "traffic", cell["traffic"] + ".json")),
        end_to_end=[m for m in bench["end_to_end"] if mine(m)],
        per_layer=[m for m in bench["per_layer"] if mine(m)])


def reader(metric_name: str):
    path = os.path.join(BENCH_DIR, "metrics", metric_name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric_name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def _cache_setup() -> None:
    """The persistent compilation cache lives at one fixed path inside the
    checkout, for JAX and for any program code that reads the variable."""
    import jax

    path = os.path.join(CACHE_DIR, "jax")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, *, allow_cpu=False, overrides=None, fault=None) -> int:
    """``allow_cpu``, ``overrides`` ({"cfg": {...}, "traffic": {...}}) and
    ``fault`` (a callable that wraps the engine) exist for the tests, which
    drive a whole run on the CPU in interpret mode."""
    args = parse(argv)
    cell = load_cell(args.workload)
    for part, upd in (overrides or {}).items():
        getattr(cell, part).update(upd)
    cfg, mix = cell.cfg, cell.traffic

    if not allow_cpu:
        _cache_setup()
    import jax

    peaks_all = _json(os.path.join(BENCH_DIR, "peaks.json"))["devices"]
    try:
        visible = jax.devices()
    except RuntimeError as e:
        log(f"JAX found no devices: {e}")
        return 3
    platform, kind = visible[0].platform, visible[0].device_kind
    print(f"bench: workload={cell.name} seed={args.seed} platform={platform} "
          f"device_kind={kind!r} devices={len(visible)} chips={cell.chips}",
          flush=True)
    if not allow_cpu:
        if platform != "tpu":
            log(f"refusing to run on platform {platform!r}: no TPU")
            return 3
        if kind not in peaks_all:
            log(f"device_kind {kind!r} is not in bench/peaks.json")
            return 3
    if len(visible) < cell.chips:
        log(f"cell needs {cell.chips} chips, JAX sees {len(visible)}")
        return 3
    devices = visible[:cell.chips]
    peaks = peaks_all.get(kind, {})

    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.runtime.scheduler import BatchScheduler
    except ImportError as e:
        log(f"cannot import the program from {ROOT}/src: {e}")
        return 2

    import numpy as np

    sys.path.insert(0, BENCH_DIR)
    import compiles
    import datagen
    import engine as engines
    import reference
    import tracing
    import traffic

    interpret = platform != "tpu"
    lowerings = compiles.LoweringCounter()
    data = cfg["data"]
    blocks = datagen.corpus_blocks(
        args.seed, devices, rows_per_chip=cfg["corpus_per_device"],
        dim=cfg["dim"], n_modes=data["n_modes"], decay=data["decay"],
        mixture_seed=data["mixture_seed"])
    pool = datagen.query_pool(args.seed, blocks, n=data["query_pool"],
                              jitter=data["query_jitter"])
    if getattr(fault, "replaces_program", False):
        eng = fault(None, blocks=blocks, cfg=cfg)
    else:
        eng = engines.build(cfg, blocks, devices, args.seed, interpret=interpret)
        if fault is not None:
            eng = fault(eng, blocks=blocks, cfg=cfg)
    for key, val in eng.notes.items():
        print(f"bench: {key}={val}", flush=True)

    # Warm-up: real batches until two in a row lower nothing new.
    quiet, tries = 0, 0
    while quiet < 2 and tries < 24:
        before = lowerings.count
        rows = (np.arange(eng.batch) + tries * eng.batch) % len(pool)
        eng.step(pool[rows])
        quiet = quiet + 1 if lowerings.count == before else 0
        tries += 1
    step_s = []

    def timed_step(batch):
        t = time.perf_counter()
        out = eng.step(batch)
        step_s.append((t, time.perf_counter() - t))
        return out

    sched = BatchScheduler(timed_step, batch_size=eng.batch)
    # Everything set-up made (compiled programs, the index, JAX's caches)
    # lives as long as the process: freeze it out of the collector, so a
    # full collection inside the window walks only what the window makes.
    gc.collect()
    gc.freeze()
    pauses = []

    def gc_clock(phase, info):
        if phase == "start":
            pauses.append([time.perf_counter(), None, info["generation"]])
        elif pauses:
            pauses[-1][1] = time.perf_counter()

    gc.callbacks.append(gc_clock)
    setup_s = time.perf_counter() - T_START
    print(f"bench: setup_s={setup_s:.3f} warm_batches={tries}", flush=True)

    trace_dir = os.path.join(CACHE_DIR, "trace", cell.name)
    run_loop = traffic.run_open if mix["loop"] == "open" else traffic.run_closed
    lowered = lowerings.count
    with tracing.capture(trace_dir) if args.trace else contextlib.nullcontext():
        t0, sent = run_loop(sched, pool, mix, args.seconds, args.seed)
    lowered = lowerings.count - lowered
    lowerings.close()
    gc.callbacks.remove(gc_clock)
    gc.unfreeze()
    print(f"bench: programs lowered inside the window: {lowered}", flush=True)
    gc_s = [b - a for a, b, _ in pauses if b is not None]
    print(f"bench: gc pauses in the window: {len(gc_s)} "
          f"(full {sum(g == 2 for *_, g in pauses)}), {sum(gc_s):.4f} s in all, "
          f"longest {max(gc_s, default=0.0):.4f} s", flush=True)

    served = [s for s in sent if s.req.status == "served"]
    failed = len(sent) - len(served)
    done_at = [s.req.completed_at - t0 for s in served]
    window_s = max(done_at) if done_at else float(args.seconds)
    answered = sum(len(s.rows) for s in served)
    lat_ms = np.asarray([(s.req.completed_at - t0 - s.due) * 1e3 if
                         s.req.status == "served" else math.inf for s in sent])
    if mix["loop"] == "open":
        late = np.asarray([(s.req.enqueued_at - t0 - s.due) * 1e3 for s in sent])
        q = np.percentile(lat_ms, [50, 95, 99, 100])
        print(f"bench: latency_ms p50={q[0]:.3f} p95={q[1]:.3f} p99={q[2]:.3f} "
              f"max={q[3]:.3f}; generator late_ms p95="
              f"{np.percentile(late, 95):.3f} max={late.max():.3f}", flush=True)
    if step_s:
        st = np.asarray([d for _, d in step_s]) * 1e3
        med = float(np.median(st))
        print(f"bench: step_ms p50={med:.3f} p99={np.percentile(st, 99):.3f} "
              f"max={st.max():.3f} (at {step_s[int(st.argmax())][0] - t0:.2f} s) "
              f"over_2x_median={int(np.sum(st > 2 * med))} "
              f"sum_s={st.sum() / 1e3:.3f}", flush=True)
    stats = dict(sched.stats)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    print(f"bench: requests={len(sent)} served={len(served)} "
          f"queries={answered} window_s={window_s:.3f} batches={stats['batches']} "
          f"batch_fill={stats['rows'] / max(stats['batches'] * eng.batch, 1):.4f}",
          flush=True)

    shapes = dict(eng.notes, batch=eng.batch)
    if eng.free is not None:
        eng.free()
    del sched, eng
    gc.collect()

    # The comparison: every answer against the exact reference.
    q_rows = np.concatenate([s.rows for s in served]) if served else np.zeros(0, int)
    uniq, inv = np.unique(q_rows, return_inverse=True)
    ref_d, ref_i = reference.exact_knn(blocks, pool[uniq], cfg["k"])
    got_d = np.concatenate([s.req.result[0] for s in served]) if served else None
    got_i = np.concatenate([s.req.result[1] for s in served]) if served else None
    nums = (reference.check_numbers(blocks, pool[q_rows], got_d, got_i,
                                    ref_d[inv], ref_i[inv])
            if served else {c: math.inf for c in reference.CHECKS})
    print("bench: numbers " + json.dumps(nums), flush=True)
    limits = cfg["limits"]
    checks = {c: {"value": _finite(nums[c]), "limit": limits[c]} for c in limits}
    correct = failed == 0 and all(v["value"] <= v["limit"] for v in checks.values())

    device = {"platform": platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": int(peak)}
    metrics, extra = {}, {}
    if args.trace:
        tr = tracing.load(trace_dir)
        device["busy_s"] = tracing.mean_busy_s(tr)
        device["window_s"] = (tr.window[1] - tr.window[0]) / 1e9
        ctx = SimpleNamespace(trace=tr, cfg=cfg, peaks=peaks, stats=stats,
                              answered=answered, chips=len(devices),
                              notes=shapes, log=log)
        for m in cell.per_layer:
            val = reader(m["name"])(ctx)
            if val is not None:
                metrics[m["name"]] = {"value": float(val), "unit": m["unit"]}
        extra["breakdown"] = tracing.breakdown(tr)
    else:
        e2e = {"qps": answered / window_s if window_s > 0 else 0.0,
               "p95_ms": _finite(np.percentile(lat_ms, 95)) if len(lat_ms) else NOT_ANSWERED,
               "recall_at_10": 1.0 - nums["recall_deficit"],
               "setup_s": setup_s}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": float(e2e[m["name"]]), "unit": m["unit"]}

    for c, v in checks.items():
        print(f"check {c}={v['value']!r} limit={v['limit']!r}", file=sys.stderr)
    print(json.dumps({"correct": bool(correct), "attempted": len(sent),
                      "failed": failed, "metrics": metrics, "device": device,
                      **extra, "checks": checks}), flush=True)
    return 0


NOT_ANSWERED = 1e300  # stands for an infinite reading: JSON has no infinity


def _finite(x) -> float:
    return float(x) if math.isfinite(x) else NOT_ANSWERED


if __name__ == "__main__":
    sys.exit(main())
