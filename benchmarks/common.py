"""Shared benchmark fixtures: corpus, queries, ground truth, recall/QPS,
and the machine-readable BENCH_dco.json trajectory registry (perf tracked
PR-over-PR; written by benchmarks.run)."""

from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import build_estimator, exact_knn
from repro.data.pipeline import synthetic_queries, synthetic_vectors
# Canonical byte accounting, re-exported so every figure script counts the
# same way the host engines (repro.quant.screen) and the fused-scan stats
# (repro.index.ivf.FusedScanStats) do — no per-figure hand-rolled counters.
from repro.quant.accounting import (  # noqa: F401  (re-export)
    fetched_tile_bytes,
    stage2_skip_rate,
    two_stage_bytes,
)

CORPUS_N = 20000
DIM = 96
NQ = 64
K = 10


_cache: dict = {}
_records: dict = {}


def set_smoke():
    """Shrink the fixture for the CI smoke invocation (tiny corpus)."""
    global CORPUS_N, NQ
    CORPUS_N = 4000
    NQ = 16
    _cache.clear()


def record(name: str, **metrics):
    """Register a machine-readable benchmark row for BENCH_dco.json.

    Every row is stamped with run provenance (git sha, jax version, device
    kind, ISO date — ``repro.obs.export.provenance``) so the perf
    trajectory stays attributable PR-over-PR; ``scripts/bench_diff.py``
    skips the ``provenance`` key when banding."""
    if "provenance" not in _cache:  # one git/jax probe per run, not per row
        from repro.obs.export import provenance

        _cache["provenance"] = provenance()
    row = {
        k: (float(v) if isinstance(v, (int, float, np.floating)) else v)
        for k, v in metrics.items()
    }
    row["provenance"] = _cache["provenance"]
    _records[name] = row


def write_bench_json(path: str = "BENCH_dco.json"):
    payload = {
        "fixture": {"corpus_n": CORPUS_N, "dim": DIM, "nq": NQ, "k": K},
        "rows": _records,
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
    return path


def fixture():
    if "corpus" not in _cache:
        corpus = synthetic_vectors(CORPUS_N, DIM, seed=0, decay=0.06)
        queries = synthetic_queries(NQ, DIM, corpus, seed=1)
        gt_d, gt_i = exact_knn(jnp.asarray(queries), jnp.asarray(corpus), K)
        _cache.update(corpus=corpus, queries=queries, gt=np.asarray(gt_i))
    return _cache["corpus"], _cache["queries"], _cache["gt"]


def recall(ids, gt) -> float:
    ids = np.asarray(ids)
    return float(np.mean([
        len(set(ids[i].tolist()) & set(gt[i].tolist())) / gt.shape[1]
        for i in range(len(gt))
    ]))


def estimator(method: str, corpus, **kw):
    key = (method, tuple(sorted(kw.items())))
    if key not in _cache:
        _cache[key] = build_estimator(
            method, corpus, jax.random.PRNGKey(7), **kw)
    return _cache[key]


def host_tables(est):
    t = est.table
    return (np.asarray(t.dims), np.asarray(t.eps), np.asarray(t.scale))


def qps(fn, n_queries: int, *, repeats: int = 1) -> float:
    t0 = time.perf_counter()
    for _ in range(repeats):
        fn()
    dt = (time.perf_counter() - t0) / repeats
    return n_queries / dt


def emit(name: str, us_per_call: float, derived: str):
    print(f"{name},{us_per_call:.1f},{derived}")
