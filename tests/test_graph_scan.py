"""Fused graph beam-scan megakernel (repro.kernels.graph_scan) + engine.

Covers: kernel-vs-oracle parity on awkward shapes with carried-in beam
windows (fetch counters and the device-side visited bitmap included), the
wave-replay passed-parity of the fused screen against ``dco_screen_batch``
at each expansion's frozen r², fetch-elision soundness + the cross-gap
buffer-reuse counter drop, the end-to-end bit-identity of the fused engine
and the host two-stage graph screen (the acceptance property), the
sharded walk's shard-count invariance against the single-host beam oracle
(the PR-5 acceptance property) with its ledger conservation and exchange
accounting, compiled-mode + sharded-config guard rails that name the
offending value, recall/dedup behaviour, the adjacency-flat layout
invariants, and a hypothesis property over random graphs/thresholds.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.core import build_estimator, exact_knn
from repro.core.dco import dco_screen_batch
from repro.index.graph import (
    build_graph, search_graph_beam_host, search_graph_fused,
    search_graph_sharded, shard_graph_nodes,
)
from repro.kernels.ops import (
    block_table, graph_scan_kernel, graph_vis_words, on_tpu, unpack_vis,
)
from repro.kernels.ref import graph_scan_ref
from repro.quant.accounting import frontier_exchange_bytes
from repro.quant.scalar import quantize_queries_block


def _recall(ids, gt_ids):
    ids, gt_ids = np.asarray(ids), np.asarray(gt_ids)
    return np.mean([
        len(set(ids[i].tolist()) & set(gt_ids[i].tolist())) / gt_ids.shape[1]
        for i in range(len(ids))
    ])


# ``graph_idx`` lives in conftest.py now: the estimator-conformance suite
# walks the same index, so the fixture is shared session-wide.


# ---- adjacency-flat layout invariants ---------------------------------------

def test_adjacency_flat_layout(graph_idx):
    sub, g = graph_idx
    assert g.has_fused
    n = sub.shape[0]
    assert g.adj_block >= 32  # int8 sublane floor: compiled-mode legal
    assert g.adj_rot.shape[0] == n * g.adj_block
    adj_ids = np.asarray(g.adj_ids).reshape(n, g.adj_block)
    nbrs = np.asarray(g.neighbors)
    rot = np.asarray(g.corpus_rot)
    adj_rot = np.asarray(g.adj_rot).reshape(n, g.adj_block, -1)
    dim = rot.shape[1]
    for v in range(0, n, 97):  # sampled nodes
        real = nbrs[v][nbrs[v] >= 0]
        assert np.array_equal(adj_ids[v, : len(real)], real)
        assert np.all(adj_ids[v, len(real):] == -1)
        # block row j IS neighbour j's rotated vector (zero dim padding)
        np.testing.assert_array_equal(adj_rot[v, : len(real), :dim],
                                      rot[real])
        assert np.all(adj_rot[v, len(real):] >= 1e17)  # sentinel pad rows


def test_build_rejects_small_adj_block(aniso_corpus):
    with pytest.raises(ValueError, match="adj_block"):
        build_graph(np.asarray(aniso_corpus)[:64], m=12, ef_construction=8,
                    delta_d=16, quant="int8", adj_block=8)


# ---- kernel vs oracle parity on awkward shapes ------------------------------

@pytest.mark.parametrize("qn,d,block_q,ef,steps,block_d", [
    (12, 64, 8, 16, 5, 8),   # Q not a tile multiple, odd step count
    (5, 40, 4, 7, 3, 8),     # nothing 128-aligned, tiny window
    (16, 96, 8, 32, 8, 8),   # D padded 96 -> 96 (3 blocks)
    # the compiled serving shape: two 32-row query tiles, adj_block=32,
    # 256-d in Δd=128 slabs, ef=48
    (64, 256, 32, 48, 12, 128),
])
def test_graph_kernel_matches_ref(qn, d, block_q, ef, steps, block_d):
    """Kernel-vs-oracle bit parity with a carried-in (partial) beam window
    and random frontier offsets including -1 gaps and repeats."""
    rng = np.random.default_rng(qn + d)
    n = 300
    data = (rng.standard_normal((n, d)) * np.exp(-0.05 * np.arange(d))
            ).astype(np.float32)
    g = build_graph(data, m=10, ef_construction=24, delta_d=block_d,
                    quant="int8")
    est = g.estimator
    q = np.asarray(g.corpus_rot)[:qn] + 0.02 * rng.standard_normal(
        (qn, d)).astype(np.float32)
    q_tiles = (qn + block_q - 1) // block_q
    # random frontier: real node offsets with -1 gaps sprinkled in
    offs = rng.integers(0, n, (q_tiles, steps)).astype(np.int32)
    offs[rng.random((q_tiles, steps)) < 0.3] = -1
    offs[:, steps - 1] = offs[:, 0]  # a repeat exercises the reuse path
    # partial carried-in window: entry + one random node
    top_sq = np.full((qn, ef), np.inf, np.float32)
    top_ids = np.full((qn, ef), -1, np.int32)
    seed_nodes = rng.integers(0, n, qn)
    rot = np.asarray(g.corpus_rot)
    top_sq[:, 0] = np.sum((rot[seed_nodes] - q) ** 2, axis=1)
    top_ids[:, 0] = seed_nodes
    r0 = np.full((qn,), np.inf, np.float32)

    kw = dict(ef=ef, block_q=block_q, block_c=g.adj_block,
              block_d=g.scan_block_d)
    out1 = graph_scan_kernel(
        est, jnp.asarray(q), jnp.asarray(offs), jnp.asarray(top_sq),
        jnp.asarray(top_ids), jnp.asarray(r0), g.adj_rot, g.adj_codes,
        g.adj_ids, g.gscales, interpret=True, **kw)
    out2 = graph_scan_kernel(
        est, jnp.asarray(q), jnp.asarray(offs), jnp.asarray(top_sq),
        jnp.asarray(top_ids), jnp.asarray(r0), g.adj_rot, g.adj_codes,
        g.adj_ids, g.gscales, use_ref=True, **kw)
    sq1, id1, st1, vis1 = out1
    sq2, id2, st2, vis2 = out2
    assert g.adj_block == 32
    assert np.array_equal(np.asarray(id1), np.asarray(id2))
    np.testing.assert_allclose(np.asarray(sq1), np.asarray(sq2),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(st1), np.asarray(st2), rtol=1e-6)
    assert float(np.asarray(st1)[:, 0].sum()) > 0  # real two-stage work
    # the device-owned visited bitmap: kernel == oracle, and its bits are
    # exactly the real offsets each tile expanded
    assert np.array_equal(np.asarray(vis1), np.asarray(vis2))
    exp = unpack_vis(np.asarray(vis1), n)
    for t in range(q_tiles):
        want = np.zeros(n, bool)
        want[offs[t][offs[t] >= 0]] = True
        assert np.array_equal(exp[t], want)


def test_graph_kernel_compiled_matches_ref():
    """Compiled-mode parity, runnable unmodified whenever TPU hardware is
    present (128-dim fixture, scan_block_d=128, block_q from the sublane
    floor — the documented compiled-mode tile constraints)."""
    if not on_tpu():
        pytest.skip(
            "compiled Mosaic lowering needs TPU hardware; interpret-mode "
            "parity above covers the semantics")
    from repro.data.pipeline import synthetic_queries, synthetic_vectors
    from repro.kernels.ops import min_block_q

    corpus = synthetic_vectors(2000, 128, seed=0, decay=0.05)
    tq = synthetic_queries(32, 128, corpus, seed=1)
    g = build_graph(corpus, m=16, ef_construction=32, delta_d=32,
                    quant="int8", scan_block_d=128)
    bq = max(min_block_q(jnp.int8), min_block_q(jnp.float32))
    d1, i1, st1 = search_graph_fused(g, jnp.asarray(tq), k=10, ef=32,
                                     block_q=bq, interpret=False)
    d2, i2, st2 = search_graph_fused(g, jnp.asarray(tq), k=10, ef=32,
                                     block_q=bq, use_ref=True)
    assert np.array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2),
                               rtol=5e-5, atol=1e-5)
    assert st1.s1_tiles_fetched == st2.s1_tiles_fetched
    assert st1.s2_slabs_fetched == st2.s2_slabs_fetched


# ---- compiled-mode guard rails name the offending value ---------------------

def test_graph_compiled_guards_name_value(graph_idx, queries):
    sub, g = graph_idx
    q = jnp.asarray(queries)
    # block_q below the int8 sublane floor: message carries block_q=8
    with pytest.raises(ValueError, match=r"block_q=8"):
        search_graph_fused(g, q, k=10, ef=32, block_q=8, interpret=False)
    # the fixture's scan_block_d=16 slabs would not land lane-aligned
    with pytest.raises(ValueError, match=r"block_d=16"):
        search_graph_fused(g, q, k=10, ef=32, block_q=32, interpret=False)
    # sub-sublane adjacency tile: message carries block_c=16
    with pytest.raises(ValueError, match=r"block_c=16"):
        graph_scan_kernel(
            g.estimator, g.estimator.rotate(q.astype(jnp.float32)),
            jnp.zeros((3, 1), jnp.int32), jnp.full((24, 32), jnp.inf),
            jnp.full((24, 32), -1, jnp.int32), jnp.full((24,), jnp.inf),
            g.adj_rot, g.adj_codes, g.adj_ids, g.gscales,
            ef=32, block_q=32, block_c=16, block_d=128, interpret=False)


def test_ivf_compiled_guards_name_value(aniso_corpus, queries):
    """Same fail-fast contract on the IVF megakernel entry."""
    from repro.index.ivf import build_ivf, search_ivf_fused

    idx = build_ivf(aniso_corpus, n_clusters=16, quant="int8", delta_d=16)
    q = jnp.asarray(queries)
    with pytest.raises(ValueError, match=r"got 8"):
        search_ivf_fused(idx, q, k=10, n_probe=4, block_q=8,
                         interpret=False)
    with pytest.raises(ValueError, match=r"got 16"):
        search_ivf_fused(idx, q, k=10, n_probe=4, block_q=32,
                         interpret=False)


# ---- wave replay: passed-parity + fetch soundness ---------------------------

def test_graph_wave_replay_passed_parity(graph_idx, queries):
    """Replays one wave's expansions through the oracle trace and asserts,
    against ``dco_screen_batch`` at the same frozen r², that the fused
    ``passed`` set is identical, no stage-1-pruned row ever passes the
    fp32 screen, and no expansion with survivors is ever elided."""
    sub, g = graph_idx
    est = g.estimator
    block_q, ef = 8, 24
    q_rot = est.rotate(jnp.asarray(queries))
    qn = q_rot.shape[0]
    assert qn % block_q == 0
    q_tiles = qn // block_q
    rng = np.random.default_rng(0)
    n = sub.shape[0]
    steps = 6
    offs = rng.integers(0, n, (q_tiles, steps)).astype(np.int32)
    rot = np.asarray(g.corpus_rot)
    qv = np.asarray(q_rot)
    entry = int(g.entry)
    top_sq = np.full((qn, ef), np.inf, np.float32)
    top_ids = np.full((qn, ef), -1, np.int32)
    top_sq[:, 0] = np.sum((rot[entry] - qv) ** 2, axis=1)
    top_ids[:, 0] = entry
    r0 = np.minimum(np.full((qn,), np.inf, np.float32), top_sq[:, ef - 1])

    dim = q_rot.shape[1]
    eps, scale, d_pad, _ = block_table(est.table, dim, g.scan_block_d)
    qp = jnp.asarray(np.pad(qv, ((0, 0), (0, d_pad - dim))))
    qcodes, qscales = quantize_queries_block(qp, g.scan_block_d)
    vis0 = jnp.zeros((q_tiles, graph_vis_words(n)), jnp.int32)
    *out, trace = graph_scan_ref(
        jnp.asarray(offs), qcodes, qp, qscales, jnp.asarray(top_sq),
        jnp.asarray(top_ids), jnp.asarray(r0), vis0, g.adj_codes, g.adj_rot,
        g.adj_ids, g.gscales, eps, scale, ef=ef, block_q=block_q,
        block_c=g.adj_block, block_d=g.scan_block_d, return_trace=True)

    waves = pruned_rows = 0
    for rec in trace:
        i = rec["tile"]
        qs = slice(i * block_q, (i + 1) * block_q)
        rows = g.adj_rot[rec["row_start"]: rec["row_start"] + g.adj_block]
        res = dco_screen_batch(qp[qs], rows, est.table,
                               jnp.asarray(rec["rsq"]))
        valid = np.asarray(rec["valid"])[None, :]
        ref_passed = np.asarray(res.passed) & valid
        fused_passed = np.asarray(rec["passed"]) & valid
        assert np.array_equal(fused_passed, ref_passed), (
            f"passed mismatch at tile={i} step={rec['step']}")
        s1_pruned = ~np.asarray(rec["active8"]) & valid
        assert not np.any(s1_pruned & ref_passed)  # no false prunes
        assert rec["fetched"] == (rec["alive"] > 0)  # fetch soundness
        waves += 1
        pruned_rows += int(s1_pruned.sum())
    assert waves > 0 and pruned_rows > 0

    # Mask ownership: the returned bitmap holds exactly the trace's marks.
    exp = unpack_vis(np.asarray(out[3]), n)
    for t in range(q_tiles):
        marked = {r["marked"] for r in trace if r["tile"] == t}
        assert set(np.flatnonzero(exp[t]).tolist()) == marked

    # Fetch-counter drop (the cross-gap buffer-reuse fix): fresh compares
    # against the last LANDED offset, so the trace's fetch count must sit
    # at-or-below the naive previous-step rule — and strictly below it on
    # a window that revisits a tile across -1 gap steps.
    st_ref = np.asarray(out[2])
    for t in range(q_tiles):
        naive = landed = 0
        prev = last = None
        for s in range(offs.shape[1]):
            o = int(offs[t, s])
            if o >= 0:
                naive += int(o != prev)
                landed += int(o != last)
                last = o
            prev = o
        assert st_ref[t * block_q, 5] == landed <= naive
    gap_offs = np.asarray(offs, np.int32).copy()
    gap_offs[:, 1:3] = -1
    gap_offs[:, 3] = gap_offs[:, 0]  # revisit across the gap
    *out_g, _ = graph_scan_ref(
        jnp.asarray(gap_offs), qcodes, qp, qscales, jnp.asarray(top_sq),
        jnp.asarray(top_ids), jnp.asarray(r0), vis0, g.adj_codes, g.adj_rot,
        g.adj_ids, g.gscales, eps, scale, ef=ef, block_q=block_q,
        block_c=g.adj_block, block_d=g.scan_block_d, return_trace=True)
    st_gap = np.asarray(out_g[2])
    for t in range(q_tiles):
        real = gap_offs[t][gap_offs[t] >= 0]
        landed_rule = 1 + int(np.sum(real[1:] != real[:-1]))
        prev_rule = 0
        prev = None
        for s in range(gap_offs.shape[1]):
            o = int(gap_offs[t, s])
            if o >= 0 and o != prev:
                prev_rule += 1
            prev = o
        # the pre-fix rule refetches the revisited tile after the gap...
        assert prev_rule == landed_rule + 1
        # ...and the fixed counter realizes exactly that saving
        assert st_gap[t * block_q, 5] == landed_rule


# ---- hypothesis property: random graphs/windows/thresholds ------------------

@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 1000), n=st.integers(80, 250),
       d=st.sampled_from([16, 32]))
def test_graph_scan_parity_property(seed, n, d):
    """Property: for random graphs, frontiers, carried windows and (tight)
    thresholds, kernel and oracle stay bit-identical — topk, passed counts
    and DMA fetch counters included."""
    rng = np.random.default_rng(seed)
    block_d, block_q, ef, steps = 8, 4, 9, 4
    qn = 8
    data = (rng.standard_normal((n, d)) * np.exp(-0.1 * np.arange(d))
            ).astype(np.float32)
    g = build_graph(data, m=6, ef_construction=12, delta_d=block_d,
                    quant="int8")
    rot = np.asarray(g.corpus_rot)
    q = rot[:qn] + 0.05 * rng.standard_normal((qn, d)).astype(np.float32)
    q_tiles = qn // block_q
    offs = rng.integers(0, n, (q_tiles, steps)).astype(np.int32)
    offs[rng.random((q_tiles, steps)) < 0.25] = -1
    top_sq = np.full((qn, ef), np.inf, np.float32)
    top_ids = np.full((qn, ef), -1, np.int32)
    seeds = rng.integers(0, n, qn)
    top_sq[:, 0] = np.sum((rot[seeds] - q) ** 2, axis=1)
    top_ids[:, 0] = seeds
    # tight-ish random thresholds force real stage-1 pruning + elision
    d2 = np.sum((rot[None, :, :] - q[:, None, :]) ** 2, axis=2)
    r0 = (np.partition(d2, 5, axis=1)[:, 5]
          * rng.uniform(0.5, 2.0, qn)).astype(np.float32)

    kw = dict(ef=ef, block_q=block_q, block_c=g.adj_block,
              block_d=g.scan_block_d)
    sq1, id1, st1, vis1 = graph_scan_kernel(
        g.estimator, jnp.asarray(q), jnp.asarray(offs), jnp.asarray(top_sq),
        jnp.asarray(top_ids), jnp.asarray(r0), g.adj_rot, g.adj_codes,
        g.adj_ids, g.gscales, interpret=True, **kw)
    sq2, id2, st2, vis2 = graph_scan_kernel(
        g.estimator, jnp.asarray(q), jnp.asarray(offs), jnp.asarray(top_sq),
        jnp.asarray(top_ids), jnp.asarray(r0), g.adj_rot, g.adj_codes,
        g.adj_ids, g.gscales, use_ref=True, **kw)
    assert np.array_equal(np.asarray(id1), np.asarray(id2))
    np.testing.assert_allclose(np.asarray(sq1), np.asarray(sq2),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(st1), np.asarray(st2), rtol=1e-6)
    assert np.array_equal(np.asarray(vis1), np.asarray(vis2))


# ---- engine-level behaviour -------------------------------------------------

def test_fused_and_host_beam_engines_bit_identical(graph_idx, queries):
    """The acceptance property: the fused engine and the host two-stage
    graph screen walk the identical wave schedule and return bit-identical
    ids (distances to float tolerance), with matching semantic ledgers."""
    sub, g = graph_idx
    q = jnp.asarray(queries)
    d1, i1, st1 = search_graph_fused(g, q, k=10, ef=32, expand=2)
    d2, i2, st2 = search_graph_beam_host(g, q, k=10, ef=32, expand=2)
    assert np.array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2),
                               rtol=5e-5, atol=1e-5)
    assert st1.waves == st2.waves
    assert st1.bytes_per_query == st2.bytes_per_query
    assert st1.s1_tiles_fetched == st2.s1_tiles_fetched
    assert st1.s2_slabs_fetched == st2.s2_slabs_fetched
    # the structural claim fig8 quantifies: tile/slab DMA ships less than
    # row-granular gathers for the same trajectory
    assert st1.fetched_bytes_per_query < st2.gather_bytes_per_query


def test_fused_beam_recalls_and_dedups(graph_idx, queries):
    sub, g = graph_idx
    _, gt = exact_knn(jnp.asarray(queries), jnp.asarray(sub), 10)
    d, i, st = search_graph_fused(g, jnp.asarray(queries), k=10, ef=48,
                                  expand=2)
    assert _recall(i, gt) >= 0.9
    d_np = np.asarray(d)
    assert np.all(np.diff(d_np, axis=1) >= -1e-5)  # ascending
    for row in np.asarray(i):
        real = row[row >= 0]
        assert len(set(real.tolist())) == len(real)  # no duplicates
    assert st.waves > 1  # a real multi-wave walk
    assert st.avg_fp_dims < st.avg_int8_dims  # stage 1 carries the scan
    assert st.rows_per_query > 0 and st.s1_tiles_fetched > 0


def test_fused_beam_seed_r(graph_idx, queries):
    sub, g = graph_idx
    _, gt = exact_knn(jnp.asarray(queries), jnp.asarray(sub), 10)
    d0, i0, st0 = search_graph_fused(g, jnp.asarray(queries), k=10, ef=32)
    d1, i1, st1 = search_graph_fused(g, jnp.asarray(queries), k=10, ef=32,
                                     seed_r=True)
    assert _recall(i1, gt) >= _recall(i0, gt) - 0.02
    # the seeded floor can only tighten the screen: never more passed rows
    assert st1.passed_per_query <= st0.passed_per_query
    for row in np.asarray(i1):
        real = row[row >= 0]
        assert len(set(real.tolist())) == len(real)


def test_fused_beam_requires_quant_build(aniso_corpus, queries):
    g = build_graph(np.asarray(aniso_corpus)[:256], m=8, ef_construction=16,
                    delta_d=16)
    with pytest.raises(ValueError, match="quant"):
        search_graph_fused(g, jnp.asarray(queries), k=5)


def test_graph_serving_engine(graph_idx, queries):
    """--index graph serving route: the annservice engine wraps the beam
    scan behind the scheduler-shaped step and reports the fetch ledger."""
    from repro.launch.annservice import build_graph_engine

    sub, g = graph_idx
    step = build_graph_engine(g, k=10, ef=32, expand=2, block_q=8,
                              with_stats=True)
    d, i, st = step(np.asarray(queries))
    assert d.shape == (len(queries), 10) and i.shape == (len(queries), 10)
    assert st.fetched_bytes_per_query > 0
    d2, i2, _ = search_graph_fused(g, jnp.asarray(queries), k=10, ef=32,
                                   expand=2)
    assert np.array_equal(i, np.asarray(i2))


# ---- sharded beam scan: cross-shard frontier exchange -----------------------

def test_sharded_walk_shard_count_invariant(graph_idx, queries):
    """The PR-5 acceptance property: the corpus-sharded fused walk returns
    bit-identical ids (distances to float tolerance) to the single-host
    beam oracle (``num_shards=1, use_ref=True``) for every shard count,
    with the per-shard fetch ledgers summing to the single-host ledger
    (splitting a frozen wave moves work, it does not create any) and a
    nonzero exchange ledger only when shards actually exchange."""
    sub, g = graph_idx
    q = jnp.asarray(queries)
    d1, i1, s1 = search_graph_sharded(g, q, num_shards=1, k=10, ef=32,
                                      use_ref=True)
    for shards in (2, 3):
        d2, i2, s2 = search_graph_sharded(g, q, num_shards=shards, k=10,
                                          ef=32)
        assert np.array_equal(np.asarray(i1), np.asarray(i2)), shards
        np.testing.assert_allclose(np.asarray(d1), np.asarray(d2),
                                   rtol=1e-5, atol=1e-5)
        assert s2.waves == s1.waves
        assert s2.num_shards == shards
        assert len(s2.shard_fetched_bytes_per_query) == shards
        assert (sum(s2.shard_s1_tiles_fetched)
                == sum(s1.shard_s1_tiles_fetched))
        assert (sum(s2.shard_s2_slabs_fetched)
                == sum(s1.shard_s2_slabs_fetched))
        assert s2.exchange_bytes_per_wave > 0
    assert s1.exchange_bytes_per_wave == 0.0  # a single shard ships nothing


def test_sharded_oracle_and_kernel_paths_identical(graph_idx, queries):
    """Sharded fused vs sharded oracle at the same shard count: the kernel
    path and the pure-jnp replay screen identically shard by shard."""
    sub, g = graph_idx
    q = jnp.asarray(queries)
    d1, i1, s1 = search_graph_sharded(g, q, num_shards=2, k=10, ef=24)
    d2, i2, s2 = search_graph_sharded(g, q, num_shards=2, k=10, ef=24,
                                      use_ref=True)
    assert np.array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2),
                               rtol=1e-5, atol=1e-5)
    assert s1.shard_s1_tiles_fetched == s2.shard_s1_tiles_fetched
    assert s1.shard_s2_slabs_fetched == s2.shard_s2_slabs_fetched


def test_sharded_exchange_ledger_formula(graph_idx, queries):
    """The exchange ledger is the accounting helper's quantity exactly:
    waves × frontier_exchange_bytes at the walk's shapes (steps summed per
    wave, so recompute from the stats totals)."""
    sub, g = graph_idx
    n = sub.shape[0]
    q = jnp.asarray(queries)
    _, _, st = search_graph_sharded(g, q, num_shards=2, k=10, ef=32,
                                    block_q=8)
    qn = len(queries)
    q_tiles = (qn + 7) // 8
    words = graph_vis_words(n)
    # per-wave payload at steps=1 lower-bounds every wave's exchange
    floor = frontier_exchange_bytes(
        num_shards=2, queries=q_tiles * 8, ef=32,
        vis_words=q_tiles * words, q_tiles=q_tiles, steps=1)
    assert st.exchange_bytes_per_wave >= floor
    assert st.exchange_bytes_per_query == pytest.approx(
        st.exchange_bytes_per_wave * st.waves / qn)


def test_sharded_config_guards_name_value(graph_idx, queries):
    """Sharded-graph config fail-fasts name the offending value (the PR-4
    guard-rail convention): uneven node splits, nonsensical shard counts,
    multi-axis meshes, and bitmap misuse all carry the number that broke."""
    sub, g = graph_idx
    n = sub.shape[0]  # 1200
    with pytest.raises(ValueError, match=rf"n={n} % num_shards=7"):
        shard_graph_nodes(n, 7)
    with pytest.raises(ValueError, match=r"num_shards=0"):
        shard_graph_nodes(n, 0)
    with pytest.raises(ValueError, match=rf"n={n} % num_shards=7"):
        search_graph_sharded(g, jnp.asarray(queries), num_shards=7, k=10,
                             ef=32)
    # a traced-style vis_base overrunning the declared global bitmap
    with pytest.raises(ValueError, match=r"vis_base=600"):
        graph_scan_kernel(
            g.estimator, g.estimator.rotate(
                jnp.asarray(queries, jnp.float32)),
            jnp.zeros((3, 1), jnp.int32), jnp.full((24, 32), jnp.inf),
            jnp.full((24, 32), -1, jnp.int32), jnp.full((24,), jnp.inf),
            g.adj_rot, g.adj_codes, g.adj_ids, g.gscales,
            vis_base=600, vis_nodes=n, ef=32, block_q=8, block_c=g.adj_block,
            block_d=g.scan_block_d)


def test_sharded_engine_rejects_multiaxis_mesh(graph_idx):
    from repro.launch.annservice import build_sharded_graph_engine
    from repro.launch.mesh import make_mesh_compat

    sub, g = graph_idx
    mesh = make_mesh_compat((1, 1), ("data", "model"))
    with pytest.raises(ValueError, match=r"axes=\('data', 'model'\)"):
        build_sharded_graph_engine(g, mesh, k=10)


def test_bf16_adjacency_engines_bit_identical(aniso_corpus, queries):
    """The serving configuration (bf16 adjacency rows, stage 2 upcasts per
    block): fused and host beam engines stay bit-identical, the ledgers
    count 2 B per fp dim, and recall holds."""
    sub = np.asarray(aniso_corpus)[:800]
    g = build_graph(sub, m=12, ef_construction=32, delta_d=16,
                    quant="int8", adj_dtype="bfloat16")
    assert g.adj_rot.dtype == jnp.bfloat16
    q = jnp.asarray(queries)
    d1, i1, st1 = search_graph_fused(g, q, k=10, ef=24, expand=2)
    d2, i2, st2 = search_graph_beam_host(g, q, k=10, ef=24, expand=2)
    assert np.array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2),
                               rtol=5e-5, atol=1e-5)
    _, gt = exact_knn(q, jnp.asarray(sub), 10)
    assert _recall(i1, gt) >= 0.85  # bf16 rows, recall essentially intact
    # the fetched ledger counts the bf16 slab stream at 2 B/dim: it must
    # reconstruct exactly from the DMA counters
    d_pad = g.adj_rot.shape[1]
    expect = (st1.s1_tiles_fetched * g.adj_block * (d_pad + 4)
              + st1.s2_slabs_fetched * g.adj_block * g.scan_block_d * 2
              ) / len(queries)
    assert st1.fetched_bytes_per_query == pytest.approx(expect)
