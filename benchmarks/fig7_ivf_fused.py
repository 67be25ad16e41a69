"""Fig. 7 (beyond-paper): demand-paged fused IVF wave-scan vs the PR-1
two-stage host path.

The acceptance quantity for the fused subsystem: corpus bytes per query
must drop below the PR-1 two-stage flat scan (int8 prefilter + fp32
re-screen over the whole corpus, honest host accounting) at matched
recall@10.  Since the demand-paged rework (PR 3) the fused number is
DMA-granular *fetched* bytes — what HBM actually shipped: every scanned
candidate tile pays its int8 block, but the fp32 block is fetched only when
stage 1 leaves survivors, so the stage-2 skip rate converts directly into
bytes never moved.  The dims-consumed (semantic) quantity is still
recorded for trajectory continuity with PR 1/PR 2.

Emits CSV rows and registers BENCH_dco.json entries (QPS, bytes/query,
fetched bytes/query, skip rate, recall, avg dims) for PR-over-PR tracking.
"""

import time

import jax.numpy as jnp
import numpy as np

from benchmarks.common import (
    K, emit, estimator, fetched_tile_bytes, fixture, host_tables, recall,
    record,
)
from repro.index.ivf import build_ivf, search_ivf_fused
from repro.quant import quantize_corpus
from repro.quant.screen import knn_search_quant_host

# PR-2's automatic BlockSpec pipeline shipped EVERY scanned tile's fp32
# block from HBM (its @pl.when only skipped the compute); its matched-recall
# dims-consumed bytes/query on the full fixture was 424,522 (BENCH_dco.json
# trajectory — the demand-paged kernel reproduces it bit-identically).  The
# CI smoke step asserts the demand-paged *fetched* bytes/query land below
# this bar at matched recall; fig7 itself asserts the structural wins
# (skip rate > 0, fetched strictly below the non-paged fetched equivalent)
# at every fixture size.
PR2_FUSED_BYTES_PER_QUERY = 424_522


BLOCK_C = 128  # candidate-tile rows, matches search_ivf_fused's default


def _nonpaged_fetched(st, *, block_d: int, nq: int) -> float:
    """Fetched bytes/query a non-paged pipeline ships for the same scan:
    every scanned tile's fp32 slabs move whether or not stage 1 killed it."""
    elided = st.s2_slabs_total - st.s2_slabs_fetched
    return st.fetched_bytes_per_query + fetched_tile_bytes(
        elided, block_c=BLOCK_C, dims=block_d, bytes_per_dim=4) / nq


def main():
    corpus, queries, gt = fixture()
    k = gt.shape[1]
    nq = len(queries)
    est = estimator("dade", corpus, delta_d=32, p_s=0.1)

    # --- PR-1 baseline: two-stage host flat scan (real work skipping) ----
    q_rot = np.asarray(est.rotate(jnp.asarray(queries)))
    c_rot = np.asarray(est.rotate(jnp.asarray(corpus)))
    qc = quantize_corpus(jnp.asarray(c_rot))
    codes, scales = np.asarray(qc.codes), np.asarray(qc.scales)
    dims, eps, scale = host_tables(est)
    got_h, bytes_h, fp_dims_h = [], 0, 0.0
    t0 = time.perf_counter()
    for qi in range(nq):
        ids, _, stats = knn_search_quant_host(
            q_rot[qi], codes, scales, c_rot, k, dims, eps, scale, wave=256)
        got_h.append(ids)
        bytes_h += stats["bytes_scanned"]
        fp_dims_h += stats["avg_fp_dims"]
    dt_h = time.perf_counter() - t0
    r_host = recall(np.stack(got_h), gt)
    bpq_h = bytes_h / nq
    emit("fig7.host_two_stage", dt_h / nq * 1e6,
         f"recall={r_host:.3f};qps={nq/dt_h:.0f};bytes_per_q={bpq_h:.0f}")
    record("host_two_stage", recall=r_host, qps=nq / dt_h,
           bytes_per_query=bpq_h, avg_dims=fp_dims_h / nq)

    # --- fused IVF wave scan: raise n_probe until recall matches --------
    # ~312 rows per bucket (DEEP-style) regardless of fixture size, so the
    # smoke corpus doesn't degenerate into tile-sized buckets.
    n_clusters = max(8, len(corpus) // 312)
    idx = build_ivf(corpus, estimator=est, n_clusters=n_clusters,
                    quant="int8", scan_block_d=32)
    matched = None
    sweep = [p for p in (8, 16, 24, 32, 48, 64) if p < n_clusters]
    sweep.append(n_clusters)
    # block_q=4: tightest tile-probe coherence (CPU/interpret numbers; a
    # compiled TPU run needs block_q >= 32 and buys recall back with
    # n_probe — the trade is documented on search_ivf_fused).
    for n_probe in sweep:
        qj = jnp.asarray(queries)
        d, i, st = search_ivf_fused(idx, qj, k=k, n_probe=n_probe,
                                    block_q=4, block_c=BLOCK_C)  # compile
        t0 = time.perf_counter()
        d, i, st = search_ivf_fused(idx, qj, k=k, n_probe=n_probe,
                                    block_q=4, block_c=BLOCK_C)
        dt_f = time.perf_counter() - t0
        r_f = recall(i, gt)
        emit(f"fig7.fused_ivf@p{n_probe}", dt_f / nq * 1e6,
             f"recall={r_f:.3f};qps={nq/dt_f:.0f};"
             f"fetched_bytes_per_q={st.fetched_bytes_per_query:.0f};"
             f"s2_skip_rate={st.s2_skip_rate:.3f};"
             f"bytes_per_q={st.bytes_per_query:.0f};"
             f"fp_dims={st.avg_fp_dims:.2f};int8_dims={st.avg_int8_dims:.2f}")
        record(f"fused_ivf@p{n_probe}", recall=r_f, qps=nq / dt_f,
               bytes_per_query=st.bytes_per_query, avg_dims=st.avg_fp_dims,
               rows_per_query=st.rows_per_query,
               fetched_bytes_per_query=st.fetched_bytes_per_query,
               s2_skip_rate=st.s2_skip_rate)
        if matched is None and r_f >= r_host:
            matched = (n_probe, r_f, st)
    assert matched is not None, (
        f"fused IVF never reached host recall {r_host:.3f}")
    n_probe, r_f, st_m = matched
    bpq_f = st_m.bytes_per_query
    fpq_f = st_m.fetched_bytes_per_query
    reduction = bpq_h / max(bpq_f, 1.0)
    nonpaged = _nonpaged_fetched(st_m, block_d=idx.scan_block_d, nq=nq)
    emit("fig7.fused_vs_host", 0.0,
         f"matched_n_probe={n_probe};recall={r_f:.3f};"
         f"bytes_reduction={reduction:.2f}x;"
         f"fetched_bytes_per_q={fpq_f:.0f};"
         f"nonpaged_fetched_per_q={nonpaged:.0f};"
         f"s2_skip_rate={st_m.s2_skip_rate:.3f}")
    record("fused_vs_host", matched_n_probe=n_probe, recall=r_f,
           bytes_per_query=bpq_f, bytes_reduction=reduction,
           fetched_bytes_per_query=fpq_f, s2_skip_rate=st_m.s2_skip_rate,
           s2_slabs_total=st_m.s2_slabs_total,
           s2_slabs_fetched=st_m.s2_slabs_fetched,
           nonpaged_fetched_per_query=nonpaged,
           pr2_trajectory_bytes=PR2_FUSED_BYTES_PER_QUERY)
    assert bpq_f < bpq_h, (
        f"fused path must scan fewer bytes/query at matched recall: "
        f"{bpq_f:.0f} vs {bpq_h:.0f}")
    # Demand paging must elide real fp32 traffic at the matched operating
    # point: stage-2 fetched bytes strictly below total stage-2 bytes
    # (skip rate > 0), so total fetched lands strictly under what the
    # non-paged pipeline ships for the identical scan.
    assert st_m.s2_skip_rate > 0.0, (
        f"demand paging elided nothing: {st_m.s2_slabs_fetched:.0f} of "
        f"{st_m.s2_slabs_total:.0f} fp32 slabs fetched")
    assert fpq_f < nonpaged, (
        f"fetched bytes/query {fpq_f:.0f} not below the non-paged "
        f"equivalent {nonpaged:.0f}")


if __name__ == "__main__":
    main()
