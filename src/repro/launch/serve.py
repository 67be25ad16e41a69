"""DADE vector-search serving driver (module CLI).

    PYTHONPATH=src python -m repro.launch.serve --devices 1 --requests 10 \
        --corpus-per-device 16384 [--method adsampling|fdscanning]

Builds the same sharded ``search_step`` the 512-chip dry-run compiles over
the first ``--devices`` devices JAX sees; serves batched query requests
and reports QPS + recall against exact ground truth.  ``--method`` swaps
the DCO estimator so the paper's baselines are servable through the
identical stack.  ``main(argv)`` runs in-process and returns the report.

Device and kernel mode: the first line names the platform, device kind,
device count and kernel mode, and the report carries the same fields.  On
TPU the Pallas kernels compile (Mosaic), with Δd=128 and 32-row query
tiles; on any other backend they run in interpret mode, with Δd=32 and
8-row tiles, which the report names as ``kernels=interpret``.  With
``JAX_PLATFORMS=cpu`` the driver creates ``--devices`` host devices.

Telemetry (``repro.obs``): ``--metrics-json PATH`` writes the
schema-versioned metric snapshot (provenance + config echo + the byte
ledgers under their dotted names); ``--trace PATH`` installs the span
tracer and writes a Perfetto-loadable Chrome-trace of the run (per-wave
stage spans with byte attributions).  ``--open-loop RATE`` switches the
load from the closed-loop batch (submit everything, one forced drain) to
Poisson arrivals at RATE req/s with per-request latency percentiles.  The
first compiled step is excluded from every timed window by a warm-up
request; its cost is reported separately as ``compile_ms``.

Robustness (``repro.runtime.chaos``): ``--chaos SPEC`` arms fault-injection
drills (shard death with degraded-mode failover, wave stalls, step errors,
queue overload, snapshot corruption); ``--deadline-ms`` / ``--queue-watermark``
/ ``--retries`` bound latency via load shedding and bounded retry
(``serve.shed.*`` counters; ``submitted == served + shed`` always);
``--index-ckpt DIR`` warm-restarts the built index from a digest-verified
snapshot; ``--verify-degraded-oracle`` asserts a post-failover engine is
bit-identical to the surviving-corpus oracle.  docs/SERVING.md §6 is the
degraded-mode runbook.

Churn (``repro.index.mutable``): ``--mutate-rate M`` turns the graph route
into a streaming mutable index — M mutations (3:1 upsert:delete, upserts
drawn from the drifted distribution) interleave between requests, each
write-ahead logged to ``--wal`` before it is applied; an existing log is
replayed onto a fresh base at startup (the crash-recovery path, drilled by
``--chaos torn_upsert``).  A drift watchdog checks DADE staleness every
request and hot-swaps a recalibrated epsilon table behind a parity proof
(suppressed under ``--chaos stale_transform``).  ``--verify-graph-oracle``
here asserts the POST-CHURN index returns bit-identical ids to a
from-scratch rebuild of the final corpus.  docs/SERVING.md §7 is the churn
runbook.
"""

import argparse
import os


def main(argv: list[str] | None = None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=1,
                    help="serve on the first N devices JAX sees (with "
                         "JAX_PLATFORMS=cpu, N host devices are created)")
    ap.add_argument("--requests", type=int, default=10)
    ap.add_argument("--corpus-per-device", type=int, default=16384)
    ap.add_argument("--dim", type=int, default=96)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--method", default="dade",
                    choices=["dade", "adsampling", "fdscanning",
                             "pca_fixed", "rp_fixed"])
    ap.add_argument("--p-s", type=float, default=0.02)
    ap.add_argument("--index", default="flat", choices=["flat", "graph"],
                    help="flat: sharded wave scan over the whole corpus "
                         "(the default paper workload); graph: NSW index "
                         "served through the batched beam-scan megakernel "
                         "(host-built, implies --quant int8; corpus size "
                         "is the O(N·ef·M) build's budget)")
    ap.add_argument("--ef", type=int, default=48,
                    help="beam width of the --index graph route")
    ap.add_argument("--expand", type=int, default=2,
                    help="frontier expansions per query per wave "
                         "(--index graph)")
    ap.add_argument("--graph-shards", type=int, default=1,
                    help="corpus shards of the --index graph route: N > 1 "
                         "shards the adjacency-flat slab over an N-device "
                         "mesh with cross-shard frontier exchange between "
                         "waves (bit-identical to the single-host walk; "
                         "the corpus node count must divide evenly)")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous batching (--index graph): queries join "
                         "the beam-walk wave step mid-flight instead of "
                         "waiting for a full batch — per-query wave depth, "
                         "pow2-bucketed live-set compaction, retirement as "
                         "queries converge, admission from the request queue "
                         "each wave.  --graph-shards N>1 runs the "
                         "host-simulated sharded walk (per-wave slab "
                         "launches + window merge, no device mesh).  Every "
                         "retired query is bit-identical to a solo "
                         "batch-path run (docs/SERVING.md §8)")
    ap.add_argument("--max-live", type=int, default=0, metavar="SLOTS",
                    help="live-walk slot cap of --continuous (admission "
                         "stops while the live set is full); 0 = --batch")
    ap.add_argument("--slo", default="off", metavar="LO:HI[:STALL]",
                    help="SLO effort adaptation of --continuous: per-query "
                         "frontier expand adapts within [LO, HI] from the "
                         "observed threshold-tightening rate (a stalling "
                         "walk gets MORE effort so it converges inside its "
                         "budget); optional :STALL retires a walk after "
                         "STALL consecutive no-tightening waves.  'off' "
                         "(default) keeps the fixed-parameter engine — "
                         "bit-identical to batch serving")
    ap.add_argument("--verify-graph-oracle", action="store_true",
                    help="before serving, assert the --index graph engine "
                         "returns bit-identical ids to the single-host "
                         "beam oracle on a verification batch (the "
                         "sharded-serving acceptance check; exits nonzero "
                         "on mismatch)")
    ap.add_argument("--quant", default="none", choices=["none", "int8"],
                    help="int8: stream the corpus as 1-byte codes per wave "
                         "(repro.quant) with budgeted exact refinement")
    ap.add_argument("--refine-per-wave", type=int, default=0,
                    help="exact refinements per wave in --quant int8 mode "
                         "(0 = autotune from the stage-1 bound band width); "
                         "the fused megakernel route has no refine budget — "
                         "it re-screens survivors exactly in-kernel — so "
                         "this flag is inert there")
    ap.add_argument("--fused", default="auto", choices=["auto", "on", "off"],
                    help="route the --quant int8 wave scan through the fused "
                         "wave-scan megakernel (auto: TPU only; 'on' forces "
                         "interpret mode off-TPU — correct but slow)")
    ap.add_argument("--open-loop", type=float, default=0.0, metavar="RATE",
                    help="serve requests as a Poisson arrival process at "
                         "RATE req/s (open loop: arrivals don't wait for "
                         "completions) and report p50/p95/p99 per-request "
                         "latency next to QPS; 0 (default) keeps the "
                         "closed-loop batch drain")
    ap.add_argument("--metrics-json", default=None, metavar="PATH",
                    help="write the schema-versioned metrics snapshot "
                         "(repro.obs envelope: provenance, config echo, "
                         "byte-ledger counters, latency histograms) to PATH")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record the span tracer and write a "
                         "Perfetto-loadable Chrome-trace JSON of the run "
                         "to PATH (per-wave stage spans with byte "
                         "attributions; the recorded spans are fenced with "
                         "block_until_ready, so leave unset for peak QPS). "
                         "Without it the same spans still reach any "
                         "running jax.profiler capture, unfenced")
    ap.add_argument("--chaos", default=None, metavar="SPEC",
                    help="arm a fault-injection drill (repro.runtime.chaos): "
                         "';'-joined kind[:key=val]* tokens, e.g. "
                         "'shard_death:shard=1:after=2' kills shard 1 after "
                         "two healthy batches and the sharded graph engine "
                         "keeps serving in degraded mode (docs/SERVING.md §6)")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="per-request latency budget: requests still queued "
                         "past it are shed (serve.shed.deadline) instead of "
                         "dispatched; served requests that exceeded it count "
                         "serve.deadline.missed (0 = no deadline)")
    ap.add_argument("--queue-watermark", type=int, default=0, metavar="ROWS",
                    help="queue-depth watermark in query rows: submits that "
                         "would exceed it are shed at the door "
                         "(serve.shed.queue; 0 = unbounded)")
    ap.add_argument("--retries", type=int, default=0,
                    help="bounded retries per engine batch (exponential "
                         "backoff); exhausted retries shed the batch "
                         "(serve.shed.error) and serving continues")
    ap.add_argument("--retry-backoff-ms", type=float, default=20.0,
                    help="first-retry backoff (doubles per attempt)")
    ap.add_argument("--index-ckpt", default=None, metavar="DIR",
                    help="warm-restart snapshot dir: restore the built index "
                         "(graph route: graph + estimator; flat route: "
                         "estimator) from DIR instead of rebuilding, or "
                         "build once and save there; per-leaf sha256 digests "
                         "reject corrupted slabs and fall back to a rebuild")
    ap.add_argument("--mutate-rate", type=float, default=0.0, metavar="MUTS",
                    help="churn drill (--index graph, single replica): apply "
                         "MUTS mutations between requests through the "
                         "streaming mutable index (3:1 upsert:delete; "
                         "upserts drawn from the drifted distribution so "
                         "the DADE staleness watchdog has something to "
                         "catch), write-ahead logged to --wal; reports "
                         "recall under churn plus the mutate.* and "
                         "calib.drift.* metric families")
    ap.add_argument("--wal", default=None, metavar="PATH",
                    help="mutation-log path for --mutate-rate (defaults to "
                         "<--index-ckpt>/mutations.wal when a snapshot dir "
                         "is given; unset with no snapshot dir = unlogged "
                         "churn).  An existing log is REPLAYED onto a fresh "
                         "base before serving — the crash-recovery path; a "
                         "torn tail record (crash mid-append) is truncated "
                         "and the mutation it never committed is dropped")
    ap.add_argument("--verify-degraded-oracle", action="store_true",
                    help="after a --chaos shard_death drill on the sharded "
                         "graph route, assert the degraded engine returns "
                         "bit-identical ids to the surviving-corpus oracle "
                         "(single-shard reference walk with the same "
                         "tombstones; exits nonzero on mismatch)")
    args = ap.parse_args(argv)

    if args.mutate_rate > 0 and args.index != "graph":
        raise SystemExit("--mutate-rate requires --index graph (the "
                         "streaming mutable index is the graph route)")
    if args.mutate_rate > 0 and args.graph_shards != 1:
        raise SystemExit("--mutate-rate serves a single replica "
                         "(--graph-shards 1): mutable growth slabs are not "
                         "corpus-sharded")
    if args.continuous and args.index != "graph":
        raise SystemExit("--continuous requires --index graph (mid-walk "
                         "admission is a property of the wave-synchronous "
                         "beam walk)")
    if args.continuous and args.mutate_rate > 0:
        raise SystemExit("--continuous and --mutate-rate are separate "
                         "drills; run them in separate serves")

    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        os.environ.setdefault(
            "XLA_FLAGS",
            f"--xla_force_host_platform_device_count={args.devices}")

    import dataclasses
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.dade_ivf import ServiceConfig
    from repro.core import build_estimator, exact_knn
    from repro.data.pipeline import synthetic_queries, synthetic_vectors
    from repro.kernels.ops import auto_block_q, block_table, kernel_spec
    from repro.launch.annservice import build_search_step, search_input_specs
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_mesh_compat
    from repro.obs import (
        MetricsRegistry, Tracer, set_tracer, write_chrome_trace,
        write_metrics_json, record_graph_scan, record_graph_sharded,
        record_flat_exchange, record_fused_serve_totals, record_dco_method,
    )
    from repro.obs.trace import current_tracer

    enable_compile_cache()
    visible = jax.devices()
    if args.devices < 1 or args.devices > len(visible):
        raise SystemExit(
            f"--devices {args.devices}: JAX sees {len(visible)} "
            f"{visible[0].platform} device(s)")
    devices = visible[:args.devices]
    n_dev = len(devices)
    platform, device_kind = devices[0].platform, devices[0].device_kind
    # Kernel mode is the platform's, resolved once here and passed to every
    # engine: Mosaic-compiled on TPU, the Pallas interpreter elsewhere.  It
    # fixes the query tile (the int8 sublane floor when compiled) and Δd:
    # compiled demand-paged stage 2 lands lane-aligned slabs, so Δd is 128
    # there; interpret mode keeps 32 for more checkpoints at test widths.
    interpret = platform != "tpu"
    kernels = "interpret" if interpret else "compiled"
    bq = auto_block_q(interpret)
    device_report = {"platform": platform, "device_kind": device_kind,
                     "devices": n_dev, "kernels": kernels}
    print(f"serve: platform={platform} device_kind={device_kind!r} "
          f"devices={n_dev} kernels={kernels} block_q={bq}")
    mesh = make_mesh_compat((n_dev,), ("data",), devices=devices)
    svc = ServiceConfig(
        corpus_per_device=args.corpus_per_device, dim=args.dim,
        query_batch=args.batch, k=args.k, delta_d=32 if interpret else 128,
        wave=4096, p_s=args.p_s, quant=args.quant,
        refine_per_wave=args.refine_per_wave)

    n = n_dev * svc.corpus_per_device
    corpus = synthetic_vectors(n, svc.dim, seed=0)

    from repro.runtime.chaos import (corrupt_checkpoint_leaf, current_chaos,
                                     parse_chaos, set_chaos)
    from repro.runtime.scheduler import BatchScheduler

    # Telemetry: the registry always collects (writing is opt-in); the
    # tracer is installed only under --trace so the default serving path
    # keeps NULL_TRACER in every instrumented loop: nothing recorded, no
    # fences, spans only while a profiler capture runs.
    reg = MetricsRegistry()
    tracer = Tracer(tool="serve", index=args.index) if args.trace else None
    set_tracer(tracer)

    # Chaos: same null-object pattern — with no --chaos the module-level
    # NULL_CHAOS stays installed and every hook in the scheduler and the
    # wave loops is a no-op, so results are bit-identical to a drill-free
    # build.
    chaos = parse_chaos(args.chaos, registry=reg) if args.chaos else None
    set_chaos(chaos)
    if chaos is not None:
        print("chaos: armed " + "; ".join(
            s.kind + (f"(shard={s.shard})" if s.shard >= 0 else "")
            for s in chaos.specs))
    if args.deadline_ms:
        reg.gauge("serve.deadline.budget_ms").set(args.deadline_ms)

    def maybe_corrupt_snapshot(directory: str) -> None:
        """slab_corruption drill: flip one byte of a committed snapshot
        leaf (only when one exists) so the restore-time digest MUST catch
        it — proving the integrity check, not assuming it."""
        step_dir = os.path.join(directory, f"step_{0:09d}")
        if not os.path.isdir(step_dir):
            return
        spec = current_chaos().take_corruption()
        if spec is not None:
            path = corrupt_checkpoint_leaf(step_dir, leaf=spec.leaf)
            print(f"chaos: corrupted snapshot leaf {spec.leaf} ({path})")

    # Estimator: the flat route can warm-restart it from --index-ckpt (the
    # graph route snapshots the whole index, estimator included, below).
    est = None
    est_cfg = {"corpus": n, "dim": svc.dim, "method": args.method,
               "p_s": svc.p_s, "delta_d": svc.delta_d}
    if args.index == "flat" and args.index_ckpt:
        from repro.checkpoint.index_io import load_estimator, save_estimator

        maybe_corrupt_snapshot(args.index_ckpt)
        try:
            est = load_estimator(args.index_ckpt, expect_config=est_cfg)
        except IOError as e:
            print(f"index-ckpt: {e}; recalibrating")
        if est is not None:
            reg.counter("serve.ckpt.restored").add(1)
            print(f"index-ckpt: restored estimator from {args.index_ckpt}")
    if est is None:
        fixed_dim = svc.dim // 2 if args.method.endswith("_fixed") else None
        est = build_estimator(args.method, corpus[:50000],
                              jax.random.PRNGKey(0),
                              p_s=svc.p_s, delta_d=svc.delta_d,
                              fixed_dim=fixed_dim)
        if args.index == "flat" and args.index_ckpt:
            save_estimator(args.index_ckpt, est, config=est_cfg)
            reg.counter("serve.ckpt.saved").add(1)
            print(f"index-ckpt: saved estimator to {args.index_ckpt}")
    # Every serving engine (blocked host screen, fused megakernels) retires
    # surviving rows with the exact full-D distance; estimators whose
    # terminal estimate is approximate (the fixed-dim baselines) cannot be
    # expressed here — refuse by name BEFORE any engine builds, instead of
    # silently serving different semantics under the requested flag.
    kernel_spec(est, svc.dim, svc.delta_d)
    eps, scale, d_pad, eps_lo = block_table(est.table, svc.dim, svc.delta_d)
    c_rot = np.pad(np.asarray(est.rotate(jnp.asarray(corpus))),
                   ((0, 0), (0, d_pad - svc.dim)))

    config_echo = {k.replace("-", "_"): v for k, v in vars(args).items()}
    config_echo.update(corpus=n, d_pad=d_pad, delta_d=svc.delta_d,
                       block_q=bq, **device_report)
    verified: list[str] = []  # acceptance checks that ran and passed

    def request_recalls(pairs):
        """Mean recall@k per SERVED request vs its exact ground truth
        (``pairs`` is [(request, gt), ...] — shed requests have no result
        and never enter a recall figure)."""
        return [
            np.mean([len(set(req.result[1][i]) & set(gt[i])) / svc.k
                     for i in range(len(gt))])
            for req, gt in pairs]

    def serve_accounting(sched, reqs, gts):
        """Split the run into served/shed, book the legacy counters, and
        enforce the terminal-status invariant: every submitted request is
        exactly one of served / shed_queue / shed_deadline / shed_error
        (the metrics schema check re-asserts this on the snapshot)."""
        served = [(r, g) for r, g in zip(reqs, gts) if r.status == "served"]
        shed = sum(sched.stats[k] for k in
                   ("shed_queue", "shed_deadline", "shed_error"))
        assert sched.stats["submitted"] == sched.stats["served"] + shed, \
            sched.stats
        assert all(r.result is not None for r, _ in served)
        # Legacy counters keep their pre-PR meaning (completed work), so
        # the latency-histogram-count == serve.requests check stays valid.
        reg.counter("serve.requests").add(len(served))
        reg.counter("serve.queries").add(sum(len(g) for _, g in served))
        return served, shed

    def shed_note(sched) -> str:
        s = sched.stats
        if not any(s[k] for k in ("shed_queue", "shed_deadline",
                                  "shed_error", "retries")):
            return ""
        return (f" shed(queue={s['shed_queue']} deadline={s['shed_deadline']}"
                f" error={s['shed_error']}) retries={s['retries']}")

    def degraded_split(served) -> tuple[str, dict]:
        """Recall split between healthy and degraded (dead-shard) batches:
        the recall delta IS the cost of failover, measured on this run's
        own traffic rather than asserted."""
        deg = [(r, g) for r, g in served if r.degraded]
        if not deg:
            return "", {}
        healthy = [(r, g) for r, g in served if not r.degraded]
        dr = float(np.mean(request_recalls(deg)))
        delta = (float(np.mean(request_recalls(healthy))) - dr
                 if healthy else 0.0)
        reg.counter("graph.sharded.degraded.requests").add(len(deg))
        reg.gauge("graph.sharded.degraded.recall").set(dr)
        reg.gauge("graph.sharded.degraded.recall_delta").set(delta)
        note = (f" degraded(requests={len(deg)} recall={dr:.3f}"
                f" delta={delta:+.3f})")
        return note, {"degraded_requests": len(deg), "degraded_recall": dr,
                      "degraded_recall_delta": delta}

    def warmup(step_fn, queries_np) -> float:
        """Run ONE engine step outside every timed window and return its
        wall-clock ms.  The first step pays jit tracing + compilation; the
        old driver booked that into the closed-loop QPS figure, which
        penalized exactly the routes with the biggest kernels."""
        t0 = time.perf_counter()
        with current_tracer().span("serve.warmup"):
            step_fn(queries_np)
        ms = (time.perf_counter() - t0) * 1e3
        reg.gauge("serve.compile_ms").set(ms)
        return ms

    def drive(sched, payloads):
        """Push the prepared (queries, gt) payloads through the scheduler.

        Closed loop (default): enqueue everything, one forced drain —
        batch throughput, the bench-comparable number.  Open loop
        (--open-loop RATE): submit at Poisson arrival times, draining
        opportunistically — per-request latency under load, the SLO
        number.  Returns (reqs, gts, wall_dt, latencies_ms); latency is
        completion-to-enqueue per request (queue wait included — in an
        open loop that wait IS the latency story).
        """
        lat = reg.histogram("serve.request.latency_ms")
        reqs, gts, lat_ms = [], [], []
        deadline_s = args.deadline_ms / 1e3 if args.deadline_ms else None

        def collect(done):
            t_done = time.perf_counter()
            for req in done:
                # completed_at is stamped by the scheduler at the serving
                # instant — under continuous batching one drain completes
                # requests across many waves, so collect-time would
                # overstate every latency but the last one's.
                t_req = req.completed_at or t_done
                ms = (t_req - req.enqueued_at) * 1e3
                lat.observe(ms)
                lat_ms.append(ms)
                # Served but late: the answer arrived past its budget (the
                # request was already dispatched when the budget expired —
                # shedding it mid-engine would waste the batch).
                if req.deadline_at is not None and t_req > req.deadline_at:
                    reg.counter("serve.deadline.missed").add(1)

        t0 = time.perf_counter()
        with current_tracer().span("serve.drive",
                                   open_loop=args.open_loop > 0):
            if args.open_loop > 0:
                arr = np.random.default_rng(17).exponential(
                    1.0 / args.open_loop, size=len(payloads))
                t_next = t0
                for (q, gt), gap in zip(payloads, arr):
                    t_next += gap
                    now = time.perf_counter()
                    if t_next > now:
                        time.sleep(t_next - now)
                    reqs.append(sched.submit(q, deadline_s=deadline_s))
                    gts.append(gt)
                    collect(sched.drain(force=False))
                collect(sched.drain(force=True))
            else:
                for q, gt in payloads:
                    reqs.append(sched.submit(q, deadline_s=deadline_s))
                    gts.append(gt)
                collect(sched.drain(force=True))
        dt = time.perf_counter() - t0
        return reqs, gts, dt, lat_ms

    def latency_note(lat_ms) -> str:
        if not lat_ms:
            return ""
        lat = reg.histogram("serve.request.latency_ms")
        reg.gauge("serve.request.p50_ms").set(lat.percentile(50))
        reg.gauge("serve.request.p95_ms").set(lat.percentile(95))
        reg.gauge("serve.request.p99_ms").set(lat.percentile(99))
        return (f" latency_ms(p50={lat.percentile(50):.1f}"
                f" p95={lat.percentile(95):.1f}"
                f" p99={lat.percentile(99):.1f})")

    def emit(report: dict) -> dict:
        """Write the machine-readable outputs next to the printed line and
        return the report, tagged with the device, the kernel mode and the
        acceptance checks that passed."""
        report = dict(report, verified=list(verified), **device_report)
        # Tag the snapshot with the DCO method that answered this run's
        # queries (the method dimension rides in the counter NAME —
        # dco.method.<method>; the schema check cross-foots it against
        # serve.queries).  Emitted here so every route — flat, graph,
        # sharded, churn — carries the tag.
        record_dco_method(reg, args.method,
                          queries=reg.counter("serve.queries").value)
        for key, val in report.items():
            if isinstance(val, (int, float)):
                reg.gauge(f"serve.report.{key}").set(val)
        if args.metrics_json:
            write_metrics_json(reg, args.metrics_json, config=config_echo,
                               extra={"report": report})
            print(f"metrics-json: wrote {args.metrics_json}")
        if tracer is not None:
            write_chrome_trace(tracer, args.trace)
            print(f"trace: wrote {args.trace} "
                  f"({len(tracer.events)} events)")
        set_tracer(None)
        set_chaos(None)
        return report

    def make_scheduler(step_fn) -> BatchScheduler:
        return BatchScheduler(
            step_fn, batch_size=svc.query_batch,
            max_queue_rows=args.queue_watermark,
            max_retries=args.retries,
            retry_backoff_s=args.retry_backoff_ms / 1e3,
            registry=reg)

    def make_payloads(prep):
        """Precompute every request's queries + exact ground truth BEFORE
        the clock starts — gt is evaluation harness, not serving work."""
        rng = np.random.default_rng(9)
        payloads = []
        corpus_gt = jnp.asarray(corpus)  # one host-to-device copy
        for r in range(args.requests):
            nq = int(rng.integers(svc.query_batch // 2,
                                  2 * svc.query_batch))
            q = synthetic_queries(nq, svc.dim, corpus, seed=100 + r)
            _, gt = exact_knn(jnp.asarray(q), corpus_gt, svc.k)
            payloads.append((prep(q), np.asarray(gt)))
        return payloads

    if args.index == "graph" and args.mutate_rate > 0:
        # Streaming churn route (ISSUE 8): the graph is a MutableGraph —
        # upserts continue the builder's insertion sequence inside
        # pre-reserved capacity slabs (array-bit-identical to a rebuild of
        # the grown corpus), deletes tombstone.  Every mutation is
        # write-ahead logged BEFORE it is applied, so a crash (drilled by
        # --chaos torn_upsert, which tears a record mid-append) recovers by
        # rebuilding the base and replaying the log — and the recovered
        # index is the same index, provable against the rebuild oracle.
        from repro.checkpoint.wal import MutationLog, replay_into
        from repro.data.pipeline import drifted_vectors
        from repro.index.graph import build_graph, search_graph_fused
        from repro.index.mutable import DriftWatchdog, MutableGraph
        from repro.obs import record_drift, record_mutations
        from repro.runtime.chaos import ChaosError

        g_m, g_efc = 16, max(2 * args.ef, 64)
        n_mut = int(round(args.requests * args.mutate_rate))
        cap = n + 2 * n_mut + 64
        wal_path = args.wal or (
            os.path.join(args.index_ckpt, "mutations.wal")
            if args.index_ckpt else None)
        # Upsert traffic comes from the drifted distribution (faster
        # spectrum decay in the fitted basis), the regime where a stale
        # epsilon table over-prunes — giving the watchdog a real signal.
        pool = drifted_vectors(est.transform, max(n_mut, 1), seed=11)
        rng_m = np.random.default_rng(13)

        def fresh_base() -> MutableGraph:
            return MutableGraph(corpus, m=g_m, ef_construction=g_efc,
                                capacity=cap, estimator=est, quant="int8")

        st: dict = {}

        def boot() -> None:
            """(Re)build serving state: fresh base + WAL replay.  Called at
            startup and again after a torn-append crash — the recovered
            index equals the pre-crash applied state (the torn record was
            never applied, so truncating it is exactly correct)."""
            st["log"] = MutationLog(wal_path) if wal_path else None
            st["idx"] = fresh_base()
            st["wd"] = DriftWatchdog(corpus, reservoir=min(1024, n),
                                     p_s=svc.p_s, num_pairs=1024)
            st["ups"] = []
            log = st["log"]
            if log is not None and (log.seq or log.recovered_torn):
                recs = log.replay()
                for rec in recs:
                    if rec["op"] == "upsert":
                        st["wd"].observe(rec["vec"])
                        st["ups"].append(np.asarray(rec["vec"], np.float32))
                counts = replay_into(st["idx"], recs)
                reg.counter("serve.wal.replayed").add(len(recs))
                if log.recovered_torn:
                    reg.counter("serve.wal.recovered_torn").add(1)
                print(f"wal: replayed {counts} from {wal_path}"
                      + (" (torn tail truncated)" if log.recovered_torn
                         else ""))
            dead = {g for b, c in st["idx"].tombstones
                    for g in range(b, b + c)}
            st["live"] = [g for g in range(st["idx"].count) if g not in dead]

        boot()

        class _WalHolder:
            """Append-before-apply for recalibration swaps: the new table
            hits the log before the serving estimator, so replay reproduces
            the exact estimator history too."""

            @property
            def estimator(self):
                return st["idx"].estimator

            def set_estimator(self, e) -> None:
                if st["log"] is not None:
                    st["log"].append_set_table(e.table)
                st["idx"].set_estimator(e)

        holder = _WalHolder()

        def mutate_once() -> None:
            idx, log = st["idx"], st["log"]
            if st["live"] and rng_m.random() < 0.25:
                gid = st["live"][int(rng_m.integers(len(st["live"])))]
                if log is not None:
                    log.append_delete(gid)
                idx.delete(gid)
                st["live"].remove(gid)
                return
            vec = pool[min(idx.ledger.upserts, len(pool) - 1)]
            if idx.count >= idx.capacity:
                # Refused mutations never reach the WAL: the log holds
                # APPLIED operations only, so replay cannot diverge on a
                # capacity boundary.
                idx.ledger.applied += 1
                idx.ledger.rejected += 1
                return
            if log is not None:
                log.append_upsert(idx.count, vec)
            gid = idx.upsert(vec)
            st["wd"].observe(vec)
            st["ups"].append(np.asarray(vec, np.float32))
            st["live"].append(gid)

        def crash_recover(e: Exception) -> None:
            print(f"chaos: {e}")
            if st["log"] is not None:
                st["log"].close()
            print("chaos: simulated crash — recovering (fresh base + wal "
                  "replay)")
            boot()

        def apply_mutations(count: int) -> None:
            for _ in range(count):
                try:
                    mutate_once()
                except ChaosError as e:
                    crash_recover(e)
                    mutate_once()  # the fault is one-shot; retry commits

        def drift_tick() -> None:
            try:
                rep = st["wd"].maybe_recalibrate(holder)
            except ChaosError as e:
                crash_recover(e)
                return
            if rep["swapped"]:
                print(f"drift: stat={rep['stat']:.3f} > "
                      f"{rep['threshold']:.3f}; epsilon table recalibrated "
                      f"and hot-swapped (parity proof passed)")
            elif rep.get("suppressed"):
                print(f"drift: stat={rep['stat']:.3f} fired but swap "
                      f"suppressed (stale_transform drill)")
            elif rep["fired"]:
                print(f"drift: fired (stat={rep['stat']:.3f}) but parity "
                      f"proof failed; stale table kept")

        def m_step(batch_np):
            d, i, _ = st["idx"].search(
                jnp.asarray(batch_np, jnp.float32), k=svc.k, ef=args.ef,
                expand=args.expand, block_q=bq, interpret=interpret)
            return np.asarray(d), np.asarray(i)

        compile_ms = warmup(
            m_step, np.asarray(
                synthetic_queries(svc.query_batch, svc.dim, corpus,
                                  seed=999), np.float32))

        sched = make_scheduler(m_step)
        lat = reg.histogram("serve.request.latency_ms")
        reqs, gts, lat_ms = [], [], []
        rng_q = np.random.default_rng(9)
        deadline_s = args.deadline_ms / 1e3 if args.deadline_ms else None
        t0 = time.perf_counter()
        with current_tracer().span("serve.drive", churn=True):
            for r in range(args.requests):
                apply_mutations(int(round(args.mutate_rate)))
                drift_tick()
                nq = int(rng_q.integers(svc.query_batch // 2,
                                        2 * svc.query_batch))
                q = synthetic_queries(nq, svc.dim, corpus, seed=100 + r)
                # Ground truth against the LIVE corpus at submit time —
                # recall under churn is measured against what the index
                # should currently know, not the frozen seed corpus.
                live = np.asarray(sorted(st["live"]), np.int64)
                rows = (np.concatenate([corpus, np.stack(st["ups"])])
                        if st["ups"] else corpus)[live]
                _, gt = exact_knn(jnp.asarray(q), jnp.asarray(rows), svc.k)
                reqs.append(sched.submit(np.asarray(q, np.float32),
                                         deadline_s=deadline_s))
                gts.append(live[np.asarray(gt)])
                done = sched.drain(force=True)
                t_done = time.perf_counter()
                for req in done:
                    ms = (t_done - req.enqueued_at) * 1e3
                    lat.observe(ms)
                    lat_ms.append(ms)
        dt = time.perf_counter() - t0

        served, shed = serve_accounting(sched, reqs, gts)
        recalls = request_recalls(served)
        rec = float(np.mean(recalls)) if recalls else 0.0
        total_q = sum(len(g) for _, g in served)
        lat_note = latency_note(lat_ms)
        idx, wd = st["idx"], st["wd"]
        idx.ledger.check()
        n_tomb = idx.count - idx.live_count
        record_mutations(reg, idx.ledger, tombstones=n_tomb)
        record_drift(reg, wd)
        wal_records = st["log"].records_written if st["log"] else 0
        if st["log"] is not None:
            reg.counter("serve.wal.appended").add(wal_records)

        if args.verify_graph_oracle:
            # The churn acceptance check: the mutated index must return
            # bit-identical ids to a from-scratch build_graph over the
            # final corpus with the same tombstones (and the same — possibly
            # recalibrated — estimator).
            full = (np.concatenate([corpus, np.stack(st["ups"])])
                    if st["ups"] else corpus)
            ridx = build_graph(full, estimator=idx.estimator, m=g_m,
                               ef_construction=g_efc, quant="int8")
            vq = np.asarray(
                synthetic_queries(svc.query_batch, svc.dim, corpus, seed=77),
                np.float32)
            t = idx.tombstones
            dv, iv, _ = idx.search(jnp.asarray(vq), k=svc.k, ef=args.ef,
                                   expand=args.expand, block_q=bq,
                                   interpret=interpret)
            do, io_, _ = search_graph_fused(
                ridx, jnp.asarray(vq), k=svc.k, ef=args.ef,
                expand=args.expand, block_q=bq, tombstones=t, exclude=t,
                interpret=interpret)
            if not np.array_equal(np.asarray(iv), np.asarray(io_)):
                raise SystemExit(
                    "post-churn: mutated index ids diverge from the "
                    "from-scratch rebuild oracle")
            if not np.allclose(np.asarray(dv), np.asarray(do),
                               rtol=5e-5, atol=1e-5):
                raise SystemExit(
                    "post-churn: mutated index distances diverge from the "
                    "from-scratch rebuild oracle")
            verified.append("churn_rebuild_oracle")
            print(f"verify-churn: mutated index ({idx.ledger.upserts} "
                  f"upserts, {idx.ledger.deletes} deletes, "
                  f"{idx.ledger.requantizes} requantizes) bit-identical to "
                  f"the from-scratch rebuild ({svc.query_batch} queries)")

        print(f"method={args.method} index=graph churn corpus={n} "
              f"live={idx.live_count} requests={len(served)}/"
              f"{sched.stats['submitted']} rows={total_q} "
              f"QPS={total_q/dt:.0f} recall@{svc.k}={rec:.3f} "
              f"compile_ms={compile_ms:.0f} "
              f"mutate(applied={idx.ledger.applied} "
              f"upserts={idx.ledger.upserts} deletes={idx.ledger.deletes} "
              f"rejected={idx.ledger.rejected} "
              f"requantize={idx.ledger.requantizes} tombstones={n_tomb}) "
              f"wal(records={wal_records}) "
              f"drift(checks={wd.checks} fired={wd.fired} "
              f"recal={wd.recalibrations} suppressed={wd.suppressed} "
              f"stat={wd.last_stat:.3f})"
              f"{shed_note(sched)}{lat_note}")
        report = emit({"qps": total_q / dt, "recall": rec,
                       "compile_ms": compile_ms, "queries": total_q,
                       "requests_submitted": sched.stats["submitted"],
                       "requests_served": sched.stats["served"],
                       "requests_shed": shed,
                       "mutations_applied": idx.ledger.applied,
                       "tombstones": n_tomb,
                       "drift_fired": wd.fired,
                       "drift_recalibrations": wd.recalibrations,
                       "wal_records": wal_records})
        if st["log"] is not None:
            st["log"].close()
        return report

    if args.index == "graph":
        # Batched beam-scan route: host-built NSW graph, one megakernel
        # launch per frontier wave per shard, host frontier selection
        # between waves (the kernel owns expansion marking — the packed
        # visited bitmap rides the wave state).  --graph-shards N > 1
        # serves the corpus-sharded walk: the adjacency slab is row-sharded
        # over an N-device mesh and each wave all-gathers/merges the beam
        # windows + bitmaps across shards (docs/SERVING.md has the worked
        # launch).
        from repro.index.graph import build_graph
        from repro.launch.annservice import (
            build_graph_engine, build_sharded_graph_engine)

        # Warm-restart: the built graph (adjacency slabs, int8 codes +
        # scales, the DADE transform riding in the estimator) snapshots
        # into --index-ckpt; a restart restores it instead of paying the
        # O(N·ef·M) rebuild.  Digest failure (slab rot) or config drift
        # falls back to the rebuild — never to serving a bad slab.
        gidx = None
        graph_cfg = {"corpus": n, "dim": svc.dim, "method": args.method,
                     "m": 16, "ef_construction": max(2 * args.ef, 64),
                     "quant": "int8"}
        if args.index_ckpt:
            from repro.checkpoint.index_io import (
                load_graph_index, save_graph_index)

            maybe_corrupt_snapshot(args.index_ckpt)
            try:
                gidx = load_graph_index(args.index_ckpt,
                                        expect_config=graph_cfg)
            except IOError as e:
                print(f"index-ckpt: {e}; falling back to rebuild")
            if gidx is not None:
                reg.counter("serve.ckpt.restored").add(1)
                print(f"index-ckpt: restored graph index from "
                      f"{args.index_ckpt}")
        if gidx is None:
            t_build = time.perf_counter()
            gidx = build_graph(corpus, estimator=est, m=16,
                               ef_construction=max(2 * args.ef, 64),
                               quant="int8")
            print(f"index: built a {n}-node graph in "
                  f"{time.perf_counter() - t_build:.1f} s (host clock)")
            if args.index_ckpt:
                save_graph_index(args.index_ckpt, gidx, config=graph_cfg)
                reg.counter("serve.ckpt.saved").add(1)
                print(f"index-ckpt: saved graph index to {args.index_ckpt}")
        sharded = args.graph_shards > 1

        if args.continuous:
            # Continuous-batching route: the ContinuousGraphEngine walks
            # every live query in its own block_q tile, admits new queries
            # into free slots each wave, and retires converged walks — the
            # ContinuousScheduler front end drives admission, deadlines,
            # shedding, retries, and the closed admission ledger.
            from repro.index.graph import (
                dead_shard_tombstones, search_graph_fused,
                search_graph_sharded)
            from repro.launch.annservice import (
                ContinuousGraphEngine, parse_slo)
            from repro.runtime.scheduler import ContinuousScheduler

            max_live = args.max_live or svc.query_batch
            engine = ContinuousGraphEngine(
                gidx, k=svc.k, ef=args.ef, expand=args.expand, block_q=bq,
                num_shards=args.graph_shards, slo=parse_slo(args.slo),
                interpret=interpret)
            reg.gauge("serve.continuous.max_live").set(float(max_live))

            # Warm-up: one solo walk pays the first kernel compile outside
            # every timed window (later live-set bucket sizes compile
            # incrementally; pow2 bucketing keeps that set logarithmic).
            t0w = time.perf_counter()
            with current_tracer().span("serve.warmup"):
                engine.admit(np.asarray(
                    synthetic_queries(1, svc.dim, corpus, seed=999),
                    np.float32)[0])
                while engine.live_count():
                    engine.step()
            compile_ms = (time.perf_counter() - t0w) * 1e3
            reg.gauge("serve.compile_ms").set(compile_ms)

            def run_solo(vq):
                """Serve each row of ``vq`` concurrently through a fresh
                SLO-off engine (the oracle walks at fixed expand, so the
                effort dial must not move underneath the comparison);
                returns (dists, ids, retired) in row order."""
                veng = ContinuousGraphEngine(
                    gidx, k=svc.k, ef=args.ef, expand=args.expand,
                    block_q=bq, num_shards=args.graph_shards, slo=None,
                    interpret=interpret)
                hmap = {veng.admit(vq[i]): i for i in range(len(vq))}
                out = {}
                while veng.live_count():
                    for rq in veng.step():
                        out[hmap[rq.handle]] = rq
                return (np.stack([out[i].dists for i in range(len(vq))]),
                        np.stack([out[i].ids for i in range(len(vq))]),
                        [out[i] for i in range(len(vq))])

            if args.verify_graph_oracle:
                # The interleaving-invariance acceptance check, live: NV
                # queries walking CONCURRENTLY through the engine must be
                # bit-identical to each one served alone by the batch
                # oracle (one-query batch = the solo walk).
                nv = min(svc.query_batch, 8)
                vq = np.asarray(
                    synthetic_queries(nv, svc.dim, corpus, seed=77),
                    np.float32)
                dv, iv, _ = run_solo(vq)
                oracle = [
                    search_graph_sharded(
                        gidx, jnp.asarray(vq[i: i + 1]),
                        num_shards=args.graph_shards, k=svc.k, ef=args.ef,
                        expand=args.expand, block_q=bq, use_ref=True)
                    if sharded else
                    search_graph_fused(
                        gidx, jnp.asarray(vq[i: i + 1]), k=svc.k,
                        ef=args.ef, expand=args.expand, block_q=bq,
                        use_ref=True)
                    for i in range(nv)]
                io = np.concatenate([np.asarray(o[1]) for o in oracle])
                do = np.concatenate([np.asarray(o[0]) for o in oracle])
                if not np.array_equal(iv, io):
                    raise SystemExit(
                        "continuous serving ids diverge from the solo "
                        "batch oracle")
                if not np.allclose(dv, do, rtol=5e-5, atol=1e-5):
                    raise SystemExit(
                        "continuous serving distances diverge from the "
                        "solo batch oracle")
                verified.append("graph_solo_oracle")
                print(f"verify: continuous engine (shards="
                      f"{args.graph_shards}) bit-identical to the solo "
                      f"batch oracle ({nv} interleaved queries)")

            sched = ContinuousScheduler(
                engine, max_live=max_live,
                max_queue_rows=args.queue_watermark,
                max_retries=args.retries,
                retry_backoff_s=args.retry_backoff_ms / 1e3, registry=reg)
            payloads = make_payloads(lambda q: np.asarray(q, np.float32))
            reqs, gts, dt, lat_ms = drive(sched, payloads)
            served, shed = serve_accounting(sched, reqs, gts)
            recalls = request_recalls(served)
            rec = float(np.mean(recalls)) if recalls else 0.0
            total_q = sum(len(g) for _, g in served)
            for st in sched.scan_stats:
                if sharded:
                    record_graph_sharded(reg, st, queries=1)
                else:
                    record_graph_scan(reg, st, queries=1)
            s = sched.stats
            occupancy = s["live_rows"] / max(s["waves"], 1)
            mean_depth = (np.mean([st.waves for st in sched.scan_stats])
                          if sched.scan_stats else 0.0)
            fetched = (np.mean([st.fetched_bytes_per_query
                                for st in sched.scan_stats])
                       if sched.scan_stats else 0.0)
            lat_note = latency_note(lat_ms)
            deg_note, deg_report = degraded_split(served)

            if args.verify_degraded_oracle:
                # The mid-walk failover acceptance check: queries ADMITTED
                # after a shard death (the live set was mid-walk when it
                # hit) must be bit-identical to the surviving-corpus
                # oracle — same contract as the batch route, but admission
                # happens into a degraded RUNNING engine.
                dead = current_chaos().dead_shards(args.graph_shards)
                if not dead:
                    print("verify-degraded: no dead shards at end of run; "
                          "nothing to check")
                else:
                    tombs = dead_shard_tombstones(n, args.graph_shards,
                                                  dead)
                    nv = min(svc.query_batch, 8)
                    vq = np.asarray(
                        synthetic_queries(nv, svc.dim, corpus, seed=78),
                        np.float32)
                    dv, iv, rqs = run_solo(vq)
                    if not all(r.degraded for r in rqs):
                        raise SystemExit(
                            "post-death admissions not flagged degraded")
                    oracle = [search_graph_sharded(
                        gidx, jnp.asarray(vq[i: i + 1]), num_shards=1,
                        k=svc.k, ef=args.ef, expand=args.expand,
                        block_q=bq, use_ref=True, tombstones=tombs)
                        for i in range(nv)]
                    io = np.concatenate([np.asarray(o[1]) for o in oracle])
                    do = np.concatenate([np.asarray(o[0]) for o in oracle])
                    if not np.array_equal(iv, io):
                        raise SystemExit(
                            "continuous degraded serving ids diverge from "
                            "the surviving-corpus oracle")
                    if not np.allclose(dv, do, rtol=5e-5, atol=1e-5):
                        raise SystemExit(
                            "continuous degraded serving distances diverge "
                            "from the surviving-corpus oracle")
                    verified.append("degraded_oracle")
                    print(f"verify-degraded: continuous admissions with "
                          f"dead shards {sorted(dead)} bit-identical to "
                          f"the surviving-corpus oracle ({nv} queries)")

            print(f"method={args.method} index=graph mode=continuous "
                  f"shards={args.graph_shards} corpus={n} "
                  f"requests={len(served)}/{s['submitted']} rows={total_q} "
                  f"ef={args.ef} expand={args.expand} max_live={max_live} "
                  f"slo={args.slo} QPS={total_q/dt:.0f} "
                  f"recall@{svc.k}={rec:.3f} compile_ms={compile_ms:.0f} "
                  f"waves={s['waves']} occupancy={occupancy:.1f} "
                  f"mean_depth={mean_depth:.1f} "
                  f"admission(admitted={s['admitted']} "
                  f"retired={s['retired']} shed={s['admission_shed']}) "
                  f"retire(frontier={s['retire_frontier']} "
                  f"budget={s['retire_budget']} "
                  f"stall={s['retire_stall']}) "
                  f"fetched_B_per_q={fetched:.0f}"
                  f"{shed_note(sched)}{deg_note}{lat_note}")
            report = {"qps": total_q / dt, "recall": rec,
                      "compile_ms": compile_ms,
                      "waves": float(s["waves"]),
                      "occupancy": float(occupancy),
                      "mean_depth": float(mean_depth),
                      "fetched_bytes_per_query": float(fetched),
                      "queries": total_q,
                      "admitted": s["admitted"], "retired": s["retired"],
                      "admission_shed": s["admission_shed"],
                      "requests_submitted": s["submitted"],
                      "requests_served": s["served"],
                      "requests_shed": shed}
            report.update(deg_report)
            return emit(report)

        if sharded:
            if args.graph_shards > n_dev:
                raise SystemExit(
                    f"--graph-shards {args.graph_shards} needs as many "
                    f"devices, got --devices {n_dev}")
            gmesh = make_mesh_compat((args.graph_shards,), ("shard",),
                                     devices=devices[:args.graph_shards])
            engine = build_sharded_graph_engine(
                gidx, gmesh, k=svc.k, ef=args.ef, expand=args.expand,
                block_q=bq, with_stats=True, interpret=interpret)
        else:
            engine = build_graph_engine(gidx, k=svc.k, ef=args.ef,
                                        expand=args.expand, block_q=bq,
                                        with_stats=True, interpret=interpret)

        if args.verify_graph_oracle:
            # The acceptance check: the serving engine must return
            # bit-identical ids to the single-host beam oracle (the
            # pure-jnp two-stage screen on the unsharded slab).
            from repro.index.graph import (
                search_graph_beam_host, search_graph_sharded)

            vq = np.asarray(
                synthetic_queries(svc.query_batch, svc.dim, corpus, seed=77),
                np.float32)
            dv, iv, _ = engine(vq)
            t_oracle = time.perf_counter()
            if sharded:
                do, io, _ = search_graph_sharded(
                    gidx, jnp.asarray(vq), num_shards=1, k=svc.k,
                    ef=args.ef, expand=args.expand, block_q=bq,
                    use_ref=True)
            else:
                do, io, _ = search_graph_beam_host(
                    gidx, jnp.asarray(vq), k=svc.k, ef=args.ef,
                    expand=args.expand, block_q=bq)
            t_oracle = time.perf_counter() - t_oracle
            if not np.array_equal(np.asarray(iv), np.asarray(io)):
                raise SystemExit(
                    "graph serving ids diverge from the single-host beam "
                    "oracle")
            if not np.allclose(np.asarray(dv), np.asarray(do),
                               rtol=5e-5, atol=1e-5):
                raise SystemExit(
                    "graph serving distances diverge from the single-host "
                    "beam oracle")
            verified.append("graph_oracle")
            print(f"verify: shards={args.graph_shards} engine bit-identical "
                  f"to the single-host beam oracle "
                  f"({svc.query_batch} queries; oracle replay "
                  f"{t_oracle:.1f} s)")

        g_stats = []

        def g_step(batch_np):
            d, i, st = engine(batch_np)
            g_stats.append(st)
            return d, i

        # Warm-up hits `engine` directly (not g_step), so the byte ledgers
        # fed to the registry cover only the timed requests.
        compile_ms = warmup(
            engine, np.asarray(
                synthetic_queries(svc.query_batch, svc.dim, corpus,
                                  seed=999), np.float32))

        sched = make_scheduler(g_step)
        payloads = make_payloads(lambda q: np.asarray(q, np.float32))
        reqs, gts, dt, lat_ms = drive(sched, payloads)
        served, shed = serve_accounting(sched, reqs, gts)
        recalls = request_recalls(served)
        rec = float(np.mean(recalls)) if recalls else 0.0
        total_q = sum(len(g) for _, g in served)
        waves = sum(st.waves for st in g_stats)
        fetched = (np.mean([st.fetched_bytes_per_query for st in g_stats])
                   if g_stats else 0.0)
        skip = (np.mean([st.s2_skip_rate for st in g_stats])
                if g_stats else 0.0)
        # Every drained batch carries the full padded query_batch rows —
        # the per-query ledgers scale back to totals by exactly that.
        for st in g_stats:
            if sharded:
                record_graph_sharded(reg, st, queries=svc.query_batch)
            else:
                record_graph_scan(reg, st, queries=svc.query_batch)
        lat_note = latency_note(lat_ms)

        if args.verify_degraded_oracle and sharded:
            # The failover acceptance check: an engine missing shards must
            # return bit-identical ids to the surviving-corpus oracle (the
            # single-shard reference walk over the same tombstoned nodes).
            from repro.index.graph import (
                dead_shard_tombstones, search_graph_sharded)

            dead = current_chaos().dead_shards(args.graph_shards)
            if not dead:
                print("verify-degraded: no dead shards at end of run; "
                      "nothing to check")
            else:
                tombs = dead_shard_tombstones(n, args.graph_shards, dead)
                vq = np.asarray(
                    synthetic_queries(svc.query_batch, svc.dim, corpus,
                                      seed=78), np.float32)
                dv, iv, _ = engine(vq)
                do, io_, _ = search_graph_sharded(
                    gidx, jnp.asarray(vq), num_shards=1, k=svc.k,
                    ef=args.ef, expand=args.expand, block_q=bq,
                    use_ref=True, tombstones=tombs)
                if not np.array_equal(np.asarray(iv), np.asarray(io_)):
                    raise SystemExit(
                        "degraded serving ids diverge from the "
                        "surviving-corpus oracle")
                if not np.allclose(np.asarray(dv), np.asarray(do),
                                   rtol=5e-5, atol=1e-5):
                    raise SystemExit(
                        "degraded serving distances diverge from the "
                        "surviving-corpus oracle")
                verified.append("degraded_oracle")
                print(f"verify-degraded: engine with dead shards "
                      f"{sorted(dead)} bit-identical to the "
                      f"surviving-corpus oracle ({svc.query_batch} queries)")
        if sharded:
            # Per-wave, per-shard fetch report + the exchange ledger: what
            # each shard's HBM ships per wave and what the interconnect
            # carries between waves (see quant/accounting.py).
            shard_fpw = [
                sum(st.shard_fetched_bytes_per_query[s] * svc.query_batch
                    for st in g_stats) / max(waves, 1.0)
                for s in range(args.graph_shards)]
            exch_pw = (np.mean([st.exchange_bytes_per_wave
                                for st in g_stats]) if g_stats else 0.0)
            exch_pq = (np.mean([st.exchange_bytes_per_query
                                for st in g_stats]) if g_stats else 0.0)
            shard_note = " ".join(
                f"shard{s}_fetched_B_per_wave={shard_fpw[s]:.0f}"
                for s in range(args.graph_shards))
            deg_note, deg_report = degraded_split(served)
            print(f"method={args.method} index=graph shards="
                  f"{args.graph_shards} corpus={n} "
                  f"requests={len(served)}/{sched.stats['submitted']} "
                  f"rows={total_q} ef={args.ef} expand={args.expand} "
                  f"QPS={total_q/dt:.0f} "
                  f"recall@{svc.k}={rec:.3f} "
                  f"compile_ms={compile_ms:.0f} "
                  f"waves={waves:.0f} fetched_B_per_q={fetched:.0f} "
                  f"{shard_note} exchange_B_per_wave={exch_pw:.0f} "
                  f"exchange_B_per_q={exch_pq:.0f} "
                  f"s2_skip_rate={skip:.3f}{shed_note(sched)}"
                  f"{deg_note}{lat_note}")
            report = {"qps": total_q / dt, "recall": rec,
                      "compile_ms": compile_ms, "waves": float(waves),
                      "fetched_bytes_per_query": float(fetched),
                      "exchange_bytes_per_wave": float(exch_pw),
                      "exchange_bytes_per_query": float(exch_pq),
                      "s2_skip_rate": float(skip), "queries": total_q,
                      "requests_submitted": sched.stats["submitted"],
                      "requests_served": sched.stats["served"],
                      "requests_shed": shed}
            report.update(deg_report)
            return emit(report)
        gather = (np.mean([st.gather_bytes_per_query for st in g_stats])
                  if g_stats else 0.0)
        print(f"method={args.method} index=graph corpus={n} "
              f"requests={len(served)}/{sched.stats['submitted']} "
              f"rows={total_q} ef={args.ef} "
              f"expand={args.expand} QPS={total_q/dt:.0f} "
              f"recall@{svc.k}={rec:.3f} "
              f"compile_ms={compile_ms:.0f} waves={waves:.0f} "
              f"fetched_B_per_q={fetched:.0f} "
              f"host_gather_B_per_q={gather:.0f} "
              f"s2_skip_rate={skip:.3f}{shed_note(sched)}{lat_note}")
        return emit({"qps": total_q / dt, "recall": rec,
                     "compile_ms": compile_ms, "waves": float(waves),
                     "fetched_bytes_per_query": float(fetched),
                     "gather_bytes_per_query": float(gather),
                     "s2_skip_rate": float(skip), "queries": total_q,
                     "requests_submitted": sched.stats["submitted"],
                     "requests_served": sched.stats["served"],
                     "requests_shed": shed})

    quant = None if args.quant == "none" else args.quant
    fused = not interpret if args.fused == "auto" else args.fused == "on"
    refine_note = ""
    if quant == "int8":
        if fused:
            # Megakernel route: per-BLOCK codes (one scale per Δd-dim
            # block) feed the int8×int8 MXU product; padded dims land in
            # an all-zero block (scale 0) and contribute nothing.
            from repro.quant import fit_block_scales, quantize_block

            bscales = fit_block_scales(jnp.asarray(c_rot), svc.delta_d)
            codes = quantize_block(jnp.asarray(c_rot), bscales, svc.delta_d)
            qc_codes, qc_scales = codes, bscales
            refine_note = " fused=megakernel"
            if args.refine_per_wave:
                refine_note += (f" refine_per_wave={args.refine_per_wave}"
                                "(inert: fused route re-screens exactly)")
        else:
            # Quantize the padded rotated corpus; padded dims get zero
            # scales (max-abs 0), so they contribute nothing to bounds or
            # distances.
            from repro.quant import quantize_corpus

            qc = quantize_corpus(jnp.asarray(c_rot))
            qc_codes, qc_scales = qc.codes, qc.scales
            if args.refine_per_wave == 0:
                from repro.launch.annservice import autotune_refine_budget

                budget, diag = autotune_refine_budget(
                    qc.scales, c_rot[:4096], k=svc.k, wave=svc.wave)
                svc = dataclasses.replace(svc, refine_per_wave=budget)
                refine_note = (f" refine_per_wave={budget}(auto,"
                               f"band={diag['band_width']:.3g},"
                               f"in_band={diag['in_band_frac']:.4f})")
            else:
                refine_note = f" refine_per_wave={args.refine_per_wave}(fixed)"
    # The demand-paged megakernel reports its fetch counters; surface the
    # fetched-vs-skipped stage-2 bytes in the serve report on that route.
    with_stats = quant == "int8" and fused
    _, shardings = search_input_specs(svc, mesh, quant=quant, fused=fused)
    step = jax.jit(build_search_step(svc, mesh, quant=quant, fused=fused,
                                     with_stats=with_stats,
                                     interpret=interpret),
                   in_shardings=shardings)
    corpus_dev = jax.device_put(c_rot.astype(np.dtype(svc.dtype)), shardings[0])
    if quant == "int8":
        codes_dev = jax.device_put(np.asarray(qc_codes), shardings[1])
        scales_dev = jax.device_put(np.asarray(qc_scales), shardings[2])

    # Variable-size requests flow through the dynamic batcher; the compiled
    # step always sees the fixed (query_batch, D) shape.
    scan_totals = np.zeros((6,), np.float64)

    def fixed_step(batch_np):
        with current_tracer().span("engine.step", route="flat",
                                   batch=len(batch_np)):
            if with_stats:
                d, i, st = step(corpus_dev, codes_dev, scales_dev,
                                jnp.asarray(batch_np), eps, scale, eps_lo)
                scan_totals[:] += np.asarray(st, np.float64)
            elif quant == "int8":
                d, i = step(corpus_dev, codes_dev, scales_dev,
                            jnp.asarray(batch_np), eps, scale, eps_lo)
            else:
                d, i = step(corpus_dev, jnp.asarray(batch_np), eps, scale,
                            eps_lo)
        return np.asarray(d), np.asarray(i)

    def prep(q):
        return np.pad(np.asarray(est.rotate(jnp.asarray(q))),
                      ((0, 0), (0, d_pad - svc.dim))
                      ).astype(np.dtype(svc.dtype))

    # Warm-up pays jit compile outside the clock; the warm-up step's scan
    # counters are discarded so the ledgers cover only timed requests.
    compile_ms = warmup(
        fixed_step,
        prep(synthetic_queries(svc.query_batch, svc.dim, corpus, seed=999)))
    scan_totals[:] = 0.0

    # Every timed flat step moves its seed pmin and top-K merge between the
    # mesh's devices (0 bytes on one device).
    from repro.quant.accounting import flat_exchange_bytes

    step_exchange = flat_exchange_bytes(
        axis_sizes=mesh.devices.shape, queries=svc.query_batch, k=svc.k)

    def exchanged_step(batch_np):
        out = fixed_step(batch_np)
        record_flat_exchange(reg, exchanged_bytes=step_exchange)
        return out

    sched = make_scheduler(exchanged_step)
    payloads = make_payloads(prep)
    reqs, gts, dt, lat_ms = drive(sched, payloads)
    served, shed = serve_accounting(sched, reqs, gts)
    recalls = request_recalls(served)
    rec = float(np.mean(recalls)) if recalls else 0.0
    total_q = sum(len(g) for _, g in served)
    lat_note = latency_note(lat_ms)
    fetch_note = ""
    report = {"qps": total_q / dt, "recall": rec,
              "compile_ms": compile_ms, "queries": total_q,
              "requests_submitted": sched.stats["submitted"],
              "requests_served": sched.stats["served"],
              "requests_shed": shed}
    if with_stats:
        # Demand-paged stage 2: every scanned wave tile ships its int8
        # block; fp32 moves in (block_c, Δd) slabs fetched only while
        # stage 2 still has active candidates.  A serving wave spans
        # wave // block_c candidate tiles, so per-wave figures divide the
        # tile counters accordingly.
        from repro.kernels.ops import flat_tile_shape
        from repro.quant.accounting import (
            ID_BYTES, fetched_tile_bytes, stage2_fetch_report,
            two_stage_bytes)

        _, block_c = flat_tile_shape(svc.query_batch, svc.wave, d_pad,
                                     svc.dtype, interpret=interpret)
        s1_tiles, s2_slabs = scan_totals[5], scan_totals[4]
        fetched, skipped, skip, _ = stage2_fetch_report(
            s1_tiles, s2_slabs, block_c=block_c, d_pad=d_pad,
            block_d=svc.delta_d, fp_bytes=np.dtype(svc.dtype).itemsize)
        waves = max(s1_tiles / (svc.wave // block_c), 1.0)
        record_fused_serve_totals(
            reg,
            s1_tiles=float(s1_tiles), s2_slabs=float(s2_slabs),
            s1_bytes=float(fetched_tile_bytes(
                s1_tiles, block_c=block_c, dims=d_pad,
                bytes_per_dim=1, id_bytes=ID_BYTES)),
            s2_bytes=float(fetched),
            sem_bytes=float(two_stage_bytes(
                scan_totals[0], scan_totals[1],
                fp_bytes=np.dtype(svc.dtype).itemsize)))
        fetch_note = (
            f" s2_fetched_B_per_wave={fetched/waves:.0f}"
            f" s2_skipped_B_per_wave={skipped/waves:.0f}"
            f" s2_skip_rate={skip:.3f}")
        report.update(s2_skip_rate=float(skip))
    print(f"method={args.method} quant={args.quant} devices={n_dev} corpus={n} "
          f"requests={len(served)}/{sched.stats['submitted']} rows={total_q} "
          f"batches={sched.stats['batches']} "
          f"pad_frac={sched.stats['padded_rows']/max(sched.stats['rows'],1):.2f} "
          f"QPS={total_q/dt:.0f} recall@{svc.k}={rec:.3f} "
          f"compile_ms={compile_ms:.0f}"
          f"{refine_note}{fetch_note}{shed_note(sched)}{lat_note}")
    return emit(report)


if __name__ == "__main__":
    main()
