"""Fig. 9 (beyond-paper): corpus-sharded graph serving — cross-shard
frontier exchange vs the single-host beam oracle.

The acceptance run for sharded beam-scan serving (the PR-5 tentpole):

  * **Bit-identity.**  The 2-shard fused walk (each shard screening only
    the frontier nodes it owns, wave-start thresholds frozen, windows and
    visited bitmaps merged between waves) must return bit-identical ids
    (distances to float tolerance) to the single-host beam oracle
    (``search_graph_sharded(num_shards=1, use_ref=True)`` — the pure-jnp
    two-stage replay on the unsharded adjacency slab).  Asserted here and
    re-asserted by the CI smoke so a silently-skipped fig9 cannot pass.
  * **Ledger conservation.**  Splitting a frozen wave across shards moves
    work between shards, it cannot create or destroy it: the per-shard
    fetch ledgers must SUM to the single-host run's ledger exactly (tile
    and slab counters, not just bytes).
  * **The price of invariance.**  Frozen-per-wave thresholds (the property
    that makes the walk shard-count-invariant) screen a few more rows than
    the in-wave-tightening single-host engine; the fused-engine comparison
    row records that overhead next to the exchange ledger
    (``quant.accounting.frontier_exchange_bytes``) so the trade is priced,
    not hidden.

This benchmark runs the host-simulated sharded driver (deterministic, no
forced device count — ``benchmarks.run`` imports jax single-device); the
mesh-backed ``shard_map`` path runs the identical arithmetic and is
asserted against the same oracle in tests/test_distributed.py and the CI
sharded serve smoke.  Wall clock on CPU runs the kernel in interpret mode
and is not meaningful (same caveat as fig7/fig8).
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, fixture, recall, record
from repro.core import build_estimator, exact_knn
from repro.index.graph import (
    build_graph, search_graph_fused, search_graph_sharded,
)

# Sub-corpus budget for the O(N·ef·M) host-side graph build (fig8 already
# pays for an 8k build; fig9 needs a smaller, shard-divisible graph).
GRAPH_NODES = 2000
M = 24
EF = 32
EXPAND = 2
BLOCK_Q = 8
SHARDS = 2


def main():
    corpus, queries, _ = fixture()
    n = min(len(corpus), GRAPH_NODES)
    n -= n % SHARDS  # the sharded walk needs an even node split
    sub = np.asarray(corpus)[:n]
    k = 10
    nq = len(queries)
    _, gt = exact_knn(jnp.asarray(queries), jnp.asarray(sub), k)
    gt = np.asarray(gt)

    est = build_estimator("dade", sub, jax.random.PRNGKey(7),
                          delta_d=32, p_s=0.1)
    t0 = time.perf_counter()
    g = build_graph(sub, estimator=est, m=M, ef_construction=48,
                    quant="int8", adj_dtype="bfloat16")
    emit("fig9.graph_build", (time.perf_counter() - t0) * 1e6,
         f"nodes={n};m={M};adj_block={g.adj_block};shards={SHARDS}")
    qj = jnp.asarray(queries)
    kw = dict(k=k, ef=EF, expand=EXPAND, block_q=BLOCK_Q)

    # --- the single-host beam oracle (frozen-wave schedule, unsharded) --
    d_o, i_o, st_o = search_graph_sharded(g, qj, num_shards=1, use_ref=True,
                                          **kw)
    r_o = recall(i_o, gt)

    # --- the 2-shard fused walk: bit-identity + ledger conservation -----
    # Traced run: the recording tracer adds fences at every wave stage,
    # and bit-identity vs the oracle must still hold below.
    from repro.obs import Tracer, use_tracer

    tr = Tracer(bench="fig9")
    t0 = time.perf_counter()
    with use_tracer(tr):
        d_s, i_s, st_s = search_graph_sharded(g, qj, num_shards=SHARDS, **kw)
    dt_s = time.perf_counter() - t0
    r_s = recall(i_s, gt)
    assert np.array_equal(np.asarray(i_s), np.asarray(i_o)), (
        "2-shard fused walk must be bit-identical to the single-host "
        "beam oracle")
    np.testing.assert_allclose(np.asarray(d_s), np.asarray(d_o),
                               rtol=5e-5, atol=1e-5)
    assert st_s.waves == st_o.waves
    assert (sum(st_s.shard_s1_tiles_fetched)
            == sum(st_o.shard_s1_tiles_fetched)), "fetch ledger not conserved"
    assert (sum(st_s.shard_s2_slabs_fetched)
            == sum(st_o.shard_s2_slabs_fetched)), "slab ledger not conserved"

    emit(f"fig9.sharded_beam@s{SHARDS}", dt_s / nq * 1e6,
         f"recall={r_s:.3f};waves={st_s.waves:.0f};"
         f"fetched_bytes_per_q={st_s.fetched_bytes_per_query:.0f};"
         f"shard_fetched="
         + "/".join(f"{b:.0f}" for b in st_s.shard_fetched_bytes_per_query)
         + f";exchange_B_per_wave={st_s.exchange_bytes_per_wave:.0f};"
         f"exchange_B_per_q={st_s.exchange_bytes_per_query:.0f}")
    record(f"graph_sharded@s{SHARDS}", recall=r_s, waves=st_s.waves,
           oracle_bit_identical=1.0,
           fetched_bytes_per_query=st_s.fetched_bytes_per_query,
           shard0_fetched_bytes_per_query=st_s.shard_fetched_bytes_per_query[0],
           shard1_fetched_bytes_per_query=st_s.shard_fetched_bytes_per_query[1],
           exchange_bytes_per_wave=st_s.exchange_bytes_per_wave,
           exchange_bytes_per_query=st_s.exchange_bytes_per_query,
           s2_skip_rate=st_s.s2_skip_rate)

    # --- the price of shard-count invariance: frozen vs tightened waves -
    d_f, i_f, st_f = search_graph_fused(g, qj, **kw)
    r_f = recall(i_f, gt)
    overhead = (st_s.fetched_bytes_per_query
                / max(st_f.fetched_bytes_per_query, 1.0))
    emit("fig9.frozen_vs_tightened", 0.0,
         f"sharded_recall={r_s:.3f};tightened_recall={r_f:.3f};"
         f"frozen_fetched_per_q={st_s.fetched_bytes_per_query:.0f};"
         f"tightened_fetched_per_q={st_f.fetched_bytes_per_query:.0f};"
         f"overhead={overhead:.2f}x")
    record("graph_sharded_vs_tightened", sharded_recall=r_s,
           tightened_recall=r_f,
           frozen_fetched_per_query=st_s.fetched_bytes_per_query,
           tightened_fetched_per_query=st_f.fetched_bytes_per_query,
           frozen_overhead=overhead)


if __name__ == "__main__":
    main()
