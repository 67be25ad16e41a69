"""Canonical DCO byte accounting — the single source of truth.

Every DADE result in this repo is ultimately a bytes-per-query claim (the
paper's DCOs are memory-bound: the win is bytes *not read*).  Three
consumers used to hand-roll their own counters — the host two-stage engines
(``repro.quant.screen``), fig6, and fig7 — which is exactly how accounting
definitions drift.  This module owns both accounting regimes:

  * **semantic (dims-consumed)** — bytes implied by the dimensions each
    row's screen actually consumed before retiring (1 B/int8 dim,
    4 B/fp32 dim).  This is what the compaction host engines physically
    read, and the PR-1/PR-2 trajectory quantity in ``BENCH_dco.json``.
  * **fetched (DMA-granular)** — bytes HBM actually shipped, at the
    granularities the demand-paged megakernel moves data in: every scanned
    candidate tile pays its full int8 block (plus the id stream), and fp32
    moves in (block_c, block_d) slabs fetched only while stage 2 still has
    valid active candidates.  The stage-2 skip rate is the fraction of
    slabs (out of tiles × slabs-per-tile) whose fetch was elided.
  * **gathered (row-granular)** — bytes a host *gather* engine ships for
    the same screen: gathers cannot read partial rows, so every screened
    candidate costs its full fp32 + int8 dims plus the id, whatever the
    screen later consumed.  This is the honest cost of the pre-megakernel
    graph path (``index.graph.search_graph`` materializes each expansion's
    ``(M, D)`` neighbour block before screening it) and the baseline the
    beam-scan engine is measured against in fig8.
  * **exchanged (cross-shard)** — bytes the sharded graph walk moves
    between shards per wave (``frontier_exchange_bytes``: all-gathered
    beam windows / thresholds / visited bitmaps + scattered frontier
    offsets).  Interconnect traffic, not HBM — reported as its own column
    by ``index.graph.GraphShardedStats``, fig9, and the sharded serve
    report.  The row-sharded flat route's share
    (``flat_exchange_bytes``: the seed's ``pmin`` and the top-K merge's
    all-gathers) feeds the same counter.

``benchmarks.common`` re-exports these helpers for the figure scripts; the
host engines import them directly (src must not depend on benchmarks).
"""

from __future__ import annotations

import math

INT8_BYTES = 1   # stage-1 code stream, bytes per dimension
FP32_BYTES = 4   # stage-2 exact rows, bytes per dimension
ID_BYTES = 4     # per-row id stream accompanying each scanned tile

__all__ = [
    "INT8_BYTES", "FP32_BYTES", "ID_BYTES",
    "two_stage_bytes", "fetched_tile_bytes", "row_gather_bytes",
    "stage2_skip_rate", "stage2_fetch_report", "frontier_exchange_bytes",
    "flat_exchange_bytes",
]


def two_stage_bytes(int8_dims, fp_dims, *, int8_bytes: int = INT8_BYTES,
                    fp_bytes: int = FP32_BYTES):
    """Semantic (dims-consumed) bytes of a two-stage screen.

    ``int8_dims`` / ``fp_dims`` are totals of dimensions consumed (arrays
    or scalars); a pure-fp32 screen is ``two_stage_bytes(0, fp_dims)``.
    """
    return int8_dims * int8_bytes + fp_dims * fp_bytes


def fetched_tile_bytes(blocks, *, block_c: int, dims: int,
                       bytes_per_dim: int, id_bytes: int = 0):
    """DMA-granular bytes of ``blocks`` fetched (block_c, dims) blocks.

    For stage-1 tiles ``dims`` is the full padded dimension; for stage-2
    slabs it is the kernel's ``block_d``.  ``id_bytes`` adds the per-row id
    stream (int32) that rides along with stage-1 tiles; stage-2 fp32
    fetches carry no ids.
    """
    return blocks * block_c * (dims * bytes_per_dim + id_bytes)


def row_gather_bytes(rows, *, dims: int, fp_bytes: int = FP32_BYTES,
                     int8_bytes: int = INT8_BYTES, id_bytes: int = ID_BYTES):
    """Row-granular bytes of a host gather engine screening ``rows``
    candidates of ``dims`` dimensions.

    A gather materializes whole rows before the screen runs, so each
    candidate pays its full fp32 row, its full int8 code row (the
    two-stage engines stream both), and its id — independent of how many
    dimensions the screen then consumed.  The graph beam-scan ledger
    (``index.graph.GraphScanStats.gather_bytes_per_query``) uses this as
    the honest host-two-stage baseline quantity."""
    return rows * (dims * (fp_bytes + int8_bytes) + id_bytes)


def frontier_exchange_bytes(*, num_shards: int, queries: int, ef: int,
                            vis_words: int, q_tiles: int, steps: int,
                            f32_bytes: int = FP32_BYTES,
                            id_bytes: int = ID_BYTES) -> float:
    """Cross-shard frontier-exchange bytes of ONE sharded beam-scan wave.

    The sharded graph walk moves two things between waves (the fourth
    ledger, next to semantic/fetched/gathered):

      * **all-gathered wave state** — each shard ships its (Q, EF) beam
        window (f32 distances + i32 ids), its (Q,) carried r², and its
        ``vis_words``-word packed visited bitmap to every other shard
        (payload × S × (S−1): the full-exchange upper bound of the
        all-gather; a ring implementation moves the same S−1 payloads per
        shard, just over fewer links);
      * **scattered frontier offsets** — the host broadcast of the wave's
        per-shard (q_tiles, steps) localized offset tables.

    Per-shard stats rides the same gather in practice but is diagnostics,
    not walk state, and is excluded.  Returns 0.0 for ``num_shards <= 1``
    (a single-host walk exchanges nothing).
    """
    if num_shards <= 1:
        return 0.0
    window = queries * ef * (f32_bytes + id_bytes) + queries * f32_bytes
    payload = window + vis_words * 4
    gathered = num_shards * (num_shards - 1) * payload
    scattered = num_shards * q_tiles * steps * 4
    return float(gathered + scattered)


def flat_exchange_bytes(*, axis_sizes, queries: int, k: int) -> float:
    """Cross-shard bytes of ONE row-sharded flat search step
    (``launch.annservice.build_search_step`` over a mesh of ``axis_sizes``).

    Per mesh axis of size A, each of the mesh's devices receives from its
    A−1 peers on that axis:

      * **the seed** (``exchange.seed``): the (Q,) f32 threshold ``pmin``;
      * **the merge** (``exchange.merge``): one hop of
        ``hierarchical_topk``, the (Q, K) f32 distances plus i32 ids.

    Counted as ``frontier_exchange_bytes`` counts, summed over every
    device: devices × (A−1) × payload per axis.  One device, or an axis of
    size 1, exchanges nothing.
    """
    payload = queries * FP32_BYTES + queries * k * (FP32_BYTES + ID_BYTES)
    devices = math.prod(axis_sizes)
    return float(sum(devices * (a - 1) * payload for a in axis_sizes))


def stage2_skip_rate(s2_slabs_fetched, s2_slabs_total) -> float:
    """Fraction of fp32 slabs (tiles × slabs-per-tile) never fetched."""
    if s2_slabs_total <= 0:
        return 0.0
    return max(0.0, 1.0 - float(s2_slabs_fetched) / float(s2_slabs_total))


def stage2_fetch_report(s1_tiles, s2_slabs, *, block_c: int, d_pad: int,
                        block_d: int, fp_bytes: int = FP32_BYTES):
    """(fetched_bytes, skipped_bytes, skip_rate, slabs_total) of the
    stage-2 slab stream.

    One place turns the kernel's DMA counters (int8 tiles fetched, fp32
    slabs fetched) into the fetched-vs-skipped stage-2 byte report, with
    the repeated-step guard: a non-fresh step can re-fetch slabs without
    adding an s1 tile, so the total never drops below the fetched count.
    """
    s2_total = max(s1_tiles * (d_pad // block_d), s2_slabs)
    fetched = fetched_tile_bytes(
        s2_slabs, block_c=block_c, dims=block_d, bytes_per_dim=fp_bytes)
    skipped = fetched_tile_bytes(
        s2_total - s2_slabs, block_c=block_c, dims=block_d,
        bytes_per_dim=fp_bytes)
    return fetched, skipped, stage2_skip_rate(s2_slabs, s2_total), s2_total
