"""Fused IVF wave-scan megakernel (repro.kernels.ivf_scan) + CSR layout.

Covers: kernel-vs-oracle parity on non-multiple-of-128 shapes (including
the demand-paged fetch counters), the no-false-prune / ``passed``-parity of
the fused screen against ``dco_screen_batch`` on aniso_corpus (replayed
wave by wave through the oracle trace), the fetch-elision soundness
property (a tile with stage-1 survivors is never elided; results stay
bit-identical to the elision-free replay), the per-block-scale error-bound
property that the parity rests on, index-level behaviour (recall, dedup,
seeding, fetch accounting), and the autotuned refine budget.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.core import build_estimator
from repro.core.dco import dco_screen_batch
from repro.index.ivf import build_ivf, search_ivf, search_ivf_fused
from repro.kernels.ops import (
    block_table, build_window_offsets, ivf_cap_tiles, ivf_scan_kernel,
    min_block_q, on_tpu,
)
from repro.kernels.ivf_scan import STATS_COLS
from repro.kernels.ref import ivf_scan_ref
from repro.kernels.tiles import merge_topk_tile
from repro.quant.scalar import (
    block_err_cum,
    fit_block_scales,
    quantize_block,
    quantize_queries_block,
)


def _recall(ids, gt_ids):
    ids, gt_ids = np.asarray(ids), np.asarray(gt_ids)
    return np.mean([
        len(set(ids[i].tolist()) & set(gt_ids[i].tolist())) / gt_ids.shape[1]
        for i in range(len(ids))
    ])


# ``fused_idx`` lives in conftest.py now: the estimator-conformance suite
# screens the same index, so the fixture is shared session-wide.


# ---- per-block scales: the error bound the kernel's soundness rests on -----

def test_block_quant_error_bound(aniso_corpus):
    est = build_estimator("dade", aniso_corpus, jax.random.PRNGKey(0), delta_d=16)
    rot = np.asarray(est.rotate(jnp.asarray(aniso_corpus)))
    block_d = 16
    bs = fit_block_scales(jnp.asarray(rot), block_d)
    codes = np.asarray(quantize_block(jnp.asarray(rot), bs, block_d))
    deq = codes.astype(np.float32) * np.repeat(np.asarray(bs), block_d)[None, :]
    err = np.abs(rot - deq)
    bound = np.repeat(np.asarray(bs) * 0.5, block_d)[None, :]
    assert np.all(err <= bound * (1 + 1e-6) + 1e-12)


def test_query_block_quant_never_clips():
    rng = np.random.default_rng(3)
    q = (rng.standard_normal((9, 48)) * 50.0).astype(np.float32)
    codes, qscales = quantize_queries_block(jnp.asarray(q), 16)
    codes, qscales = np.asarray(codes), np.asarray(qscales)
    deq = codes.astype(np.float32) * np.repeat(qscales, 16, axis=1)
    bound = np.repeat(qscales * 0.5, 16, axis=1)
    assert np.all(np.abs(q - deq) <= bound * (1 + 1e-6) + 1e-12)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 1000), n=st.integers(8, 64),
       d=st.sampled_from([16, 32, 48]))
def test_block_scale_lower_bound_property(seed, n, d):
    """Property: the fused stage-1 band never under-covers — the dequantized
    distance minus (E_c + E_q) lower-bounds the exact partial distance at
    every block checkpoint, for arbitrary data/scales/shapes.  This is the
    inequality the no-false-prune guarantee reduces to."""
    block_d = 8
    rng = np.random.default_rng(seed)
    decay = np.exp(-rng.uniform(0.01, 0.3) * np.arange(d)).astype(np.float32)
    data = (rng.standard_normal((max(n, 8), d)) * decay).astype(np.float32)
    q = (rng.standard_normal((3, d)) * decay).astype(np.float32)
    bs = fit_block_scales(jnp.asarray(data), block_d)
    codes = np.asarray(quantize_block(jnp.asarray(data), bs, block_d))
    qcodes, qscales = quantize_queries_block(jnp.asarray(q), block_d)
    deq_c = codes.astype(np.float32) * np.repeat(np.asarray(bs), block_d)[None, :]
    deq_q = np.asarray(qcodes).astype(np.float32) * np.repeat(
        np.asarray(qscales), block_d, axis=1)
    ec = np.asarray(block_err_cum(bs, block_d=block_d))  # (S,)
    eq = np.sqrt(np.cumsum(block_d * (np.asarray(qscales) * 0.5) ** 2, axis=1))
    s_count = d // block_d
    cps = (np.arange(s_count) + 1) * block_d
    for qi in range(len(q)):
        exact = np.sqrt(np.cumsum((data - q[qi]) ** 2, axis=1))[:, cps - 1]
        dq = np.sqrt(np.cumsum((deq_c - deq_q[qi]) ** 2, axis=1))[:, cps - 1]
        lb = np.maximum(dq - (ec + eq[qi])[None, :], 0.0)
        assert np.all(lb <= exact * (1 + 1e-5) + 1e-6)


# ---- kernel vs oracle parity on awkward shapes -----------------------------

@pytest.mark.parametrize("qn,d,block_q,block_c,block_d,n_probe", [
    (12, 64, 8, 64, 16, 3),   # Q not a tile multiple
    (5, 40, 4, 32, 8, 2),     # nothing 128-aligned
    (16, 96, 8, 128, 32, 4),  # D padded 96 -> 96 (3 blocks), cap window
    (16, 64, 16, 512, 16, 2),  # the flat route's wide tile: one query tile
    (64, 256, 64, 2048, 128, 2),  # the flat route's serving tile shape
])
def test_fused_kernel_matches_ref(qn, d, block_q, block_c, block_d, n_probe):
    rng = np.random.default_rng(qn + d)
    n = 700
    data = (rng.standard_normal((n, d)) * np.exp(-0.05 * np.arange(d))
            ).astype(np.float32)
    est = build_estimator("dade", data, jax.random.PRNGKey(0), delta_d=block_d)
    rot = np.asarray(est.rotate(jnp.asarray(data)))
    d_pad = (d + block_d - 1) // block_d * block_d
    max_bucket = 200
    tile = max(block_c, 128)
    n_pad = (n + max_bucket + 2 * tile + tile - 1) // tile * tile
    flat_rot = np.full((n_pad, d_pad), 1e18, np.float32)
    flat_rot[:n, :d] = rot
    flat_rot[:n, d:] = 0.0
    rot_pad = np.zeros((n, d_pad), np.float32)
    rot_pad[:, :d] = rot
    bs = fit_block_scales(jnp.asarray(rot_pad), block_d)
    flat_codes = np.zeros((n_pad, d_pad), np.int8)
    flat_codes[:n] = np.asarray(quantize_block(jnp.asarray(rot_pad), bs, block_d))
    flat_ids = np.full((n_pad,), -1, np.int32)
    flat_ids[:n] = np.arange(n)

    q = rot[:qn] + 0.02 * rng.standard_normal((qn, d)).astype(np.float32)
    q_tiles = (qn + block_q - 1) // block_q
    ws = jnp.asarray(rng.integers(0, n - max_bucket, (q_tiles, n_probe)),
                     jnp.int32)
    # unaligned starts + varying window sizes exercise the slack tile and
    # the sentinel-tail redirection of short windows
    wr = jnp.asarray(rng.integers(1, max_bucket, (q_tiles, n_probe)),
                     jnp.int32)
    r0 = jnp.full((qn,), jnp.inf)
    kw = dict(k=10, max_bucket=max_bucket, block_q=block_q, block_c=block_c,
              block_d=block_d)
    sq1, id1, st1 = ivf_scan_kernel(
        est, jnp.asarray(q), ws, wr, jnp.asarray(flat_rot),
        jnp.asarray(flat_codes), jnp.asarray(flat_ids), bs, r0,
        interpret=True, **kw)
    sq2, id2, st2 = ivf_scan_kernel(
        est, jnp.asarray(q), ws, wr, jnp.asarray(flat_rot),
        jnp.asarray(flat_codes), jnp.asarray(flat_ids), bs, r0,
        use_ref=True, **kw)
    assert np.array_equal(np.asarray(id1), np.asarray(id2))
    np.testing.assert_allclose(np.asarray(sq1), np.asarray(sq2),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(st1), np.asarray(st2), rtol=1e-6)
    # the screen actually did two-stage work
    assert float(np.asarray(st1)[:, 0].sum()) > 0
    # the merge's rounds: one count per tile, equal to the oracle's
    st1, st2 = np.asarray(st1), np.asarray(st2)
    assert st1.shape[1] == len(STATS_COLS) and STATS_COLS[6] == "merge_rounds"
    assert np.array_equal(st1[:, 6], st2[:, 6])
    assert float(st1[:, 6].sum()) > 0


def test_fused_kernel_compiled_matches_ref():
    """Compiled-mode parity, runnable unmodified whenever TPU hardware is
    present: the query tile is auto-selected from the int8 sublane floor
    (``ops.min_block_q``) and the fixture is built 128-dim with
    scan_block_d=128, the documented compiled-mode tile constraints (the
    module-level aniso fixture is 64-dim — interpret-only)."""
    block_q = max(min_block_q(jnp.int8), min_block_q(jnp.float32))
    if not on_tpu():
        pytest.skip(
            "compiled Mosaic lowering needs TPU hardware; interpret-mode "
            "parity above covers the semantics (on TPU this test runs with "
            f"auto-selected block_q={block_q})")
    from repro.data.pipeline import synthetic_queries, synthetic_vectors

    corpus = synthetic_vectors(4000, 128, seed=0, decay=0.05)
    tqueries = synthetic_queries(32, 128, corpus, seed=1)
    idx = build_ivf(corpus, n_clusters=16, quant="int8", delta_d=32,
                    scan_block_d=128)
    d1, i1, st1 = search_ivf_fused(idx, jnp.asarray(tqueries), k=10,
                                   n_probe=6, block_q=block_q,
                                   interpret=False)
    d2, i2, st2 = search_ivf_fused(idx, jnp.asarray(tqueries), k=10,
                                   n_probe=6, block_q=block_q, use_ref=True)
    assert np.array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2),
                               rtol=5e-5, atol=1e-5)
    # the hardware DMA counters must match the oracle's fetch decisions
    assert st1.s1_tiles_fetched == st2.s1_tiles_fetched
    assert st1.s2_slabs_fetched == st2.s2_slabs_fetched


def test_compiled_block_q_guard(fused_idx, queries):
    """Forcing compiled lowering with an illegal (sub-sublane) query tile
    fails fast with an actionable error instead of a Mosaic crash."""
    with pytest.raises(ValueError, match="sublane"):
        search_ivf_fused(fused_idx, jnp.asarray(queries), k=10, n_probe=4,
                         block_q=8, interpret=False)
    # the fixture's scan_block_d=16 slabs would not land lane-aligned
    with pytest.raises(ValueError, match="lane-aligned"):
        search_ivf_fused(fused_idx, jnp.asarray(queries), k=10, n_probe=4,
                         block_q=32, interpret=False)


# ---- the top-K merge against a stable sort --------------------------------

def _merge_reference(top_sq, top_ids, sq, ok, ids):
    """NumPy reference of ``tiles.merge_topk_tile``: the K least of a stable
    sort of [old top, the tile's entrants], entrants whose id is already in
    the old top removed, inf slots id -1; and the rounds the insertion
    merge runs, the most candidates any row takes out in (sq, column) order
    before its next one no longer beats its K-th."""
    k = top_sq.shape[1]
    out_sq, out_ids, taken = [], [], []
    for r in range(top_sq.shape[0]):
        new = np.where(ok[r], sq[r], np.inf)
        dup = np.isin(ids[0], top_ids[r][top_ids[r] >= 0])
        all_sq = np.concatenate([top_sq[r], np.where(dup, np.inf, new)])
        all_ids = np.concatenate([top_ids[r], ids[0]])
        order = np.argsort(all_sq, kind="stable")[:k]
        out_sq.append(all_sq[order])
        out_ids.append(np.where(np.isinf(all_sq[order]), -1, all_ids[order]))
        window, n = list(top_sq[r]), 0
        for c in np.lexsort((np.arange(new.size), new)):
            if not new[c] < window[-1]:
                break
            n += 1
            if not dup[c]:
                window.insert(int(np.searchsorted(window, new[c], "right")),
                              new[c])
                window.pop()
        taken.append(n)
    return np.stack(out_sq), np.stack(out_ids), max(taken)


@pytest.mark.parametrize("k", [1, 10, 128])
@pytest.mark.parametrize("width", [128, 2048])
def test_merge_topk_tile_matches_stable_sort(k, width):
    """Bit parity with the stable-sort reference over heavy ties, empty,
    partial and full tops, rows that pass nothing or everything, invalid
    (-1) rows, and rescanned rows whose id is already in the top at the
    same distance; the merge runs exactly the rounds the reference counts
    (one query row: that row's candidates taken out)."""
    rng = np.random.default_rng(k * 7919 + width)
    merge = jax.jit(merge_topk_tile)
    cases = 0
    for bq in (1, 8):
        for fill in ("empty", "partial", "full"):
            for levels in (4, 1000):
                ids = rng.permutation(4 * width)[:width].astype(np.int32)[None]
                ids[0, rng.random(width) < 0.1] = -1
                sq = rng.integers(0, levels, (bq, width)).astype(np.float32)
                p_ok = rng.choice([0.0, 0.01, 0.3, 1.0], bq)
                ok = (rng.random((bq, width)) < p_ok[:, None]) & (ids >= 0)
                top_sq = np.full((bq, k), np.inf, np.float32)
                top_ids = np.full((bq, k), -1, np.int32)
                for r in range(bq):
                    n = {"empty": 0, "full": k}.get(fill, int(rng.integers(1, k + 1)))
                    top_sq[r, :n] = np.sort(
                        rng.integers(0, levels, n).astype(np.float32))
                    # old entries: some are rows of this tile (a rescan),
                    # the rest rows the tile does not hold
                    pick = rng.permutation(np.concatenate([
                        ids[0][ids[0] >= 0][:max(n // 2, 1)],
                        np.arange(4 * width, 4 * width + k)]))[:n]
                    top_ids[r, :n] = pick
                    for j in range(n):
                        col = np.flatnonzero(ids[0] == pick[j])
                        if col.size and rng.random() < 0.8:
                            sq[r, col] = top_sq[r, j]
                            ok[r, col] = True
                want_sq, want_ids, want_rounds = _merge_reference(
                    top_sq, top_ids, sq, ok, ids)
                got_sq, got_ids, rounds = merge(
                    jnp.asarray(top_sq), jnp.asarray(top_ids),
                    jnp.asarray(sq), jnp.asarray(ok), jnp.asarray(ids))
                assert np.array_equal(np.asarray(got_sq), want_sq)
                assert np.array_equal(np.asarray(got_ids), want_ids)
                assert int(rounds) == want_rounds
                cases += want_rounds > 0
    assert cases > 0


# ---- demand-paged fetch elision: soundness + bit-identity property ---------

def _random_flat_layout(rng, n, d, block_d, max_bucket):
    """Random corpus in the fused kernel's flat layout (unaligned windows)."""
    data = (rng.standard_normal((n, d)) * np.exp(-0.05 * np.arange(d))
            ).astype(np.float32)
    est = build_estimator("dade", data, jax.random.PRNGKey(0), delta_d=block_d)
    rot = np.asarray(est.rotate(jnp.asarray(data)))
    d_pad = (d + block_d - 1) // block_d * block_d
    n_pad = (n + max_bucket + 2 * 128 + 127) // 128 * 128
    flat_rot = np.full((n_pad, d_pad), 1e18, np.float32)
    flat_rot[:n, :d] = rot
    flat_rot[:n, d:] = 0.0
    rot_pad = np.zeros((n, d_pad), np.float32)
    rot_pad[:, :d] = rot
    bs = fit_block_scales(jnp.asarray(rot_pad), block_d)
    flat_codes = np.zeros((n_pad, d_pad), np.int8)
    flat_codes[:n] = np.asarray(quantize_block(jnp.asarray(rot_pad), bs, block_d))
    flat_ids = np.full((n_pad,), -1, np.int32)
    flat_ids[:n] = np.arange(n)
    return est, rot, flat_rot, flat_codes, flat_ids, bs


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 1000), n=st.integers(150, 400),
       d=st.sampled_from([16, 32]))
def test_demand_paged_elision_property(seed, n, d):
    """Property: for random shapes/scales/windows/thresholds the
    demand-paged kernel (a) never elides a fetch for a tile whose oracle
    stage-1 survivor count is nonzero, and (b) keeps topk/passed/stats —
    fetch counters included — bit-identical to the oracle's elision-free
    replay of the PR-2 semantics."""
    block_d, block_q, block_c, n_probe, k = 8, 4, 32, 3, 5
    qn = 8
    max_bucket = 96
    rng = np.random.default_rng(seed)
    est, rot, flat_rot, flat_codes, flat_ids, bs = _random_flat_layout(
        rng, n, d, block_d, max_bucket)
    n_pad = flat_rot.shape[0]

    q = rot[:qn] + 0.05 * rng.standard_normal((qn, d)).astype(np.float32)
    q_tiles = qn // block_q
    ws = jnp.asarray(rng.integers(0, n - max_bucket, (q_tiles, n_probe)),
                     jnp.int32)
    wr = jnp.asarray(rng.integers(1, max_bucket, (q_tiles, n_probe)),
                     jnp.int32)
    # Finite (tight-ish) seed thresholds so stage 1 prunes whole tiles and
    # real elision happens; soundness must hold for ANY r0.
    d2 = np.sum((rot[None, :, :] - q[:, None, :]) ** 2, axis=2)
    r0 = jnp.asarray(np.partition(d2, k, axis=1)[:, k]
                     * rng.uniform(0.5, 2.0, qn).astype(np.float32))

    kw = dict(k=k, max_bucket=max_bucket, block_q=block_q, block_c=block_c,
              block_d=block_d)
    sq1, id1, st1 = ivf_scan_kernel(
        est, jnp.asarray(q), ws, wr, jnp.asarray(flat_rot),
        jnp.asarray(flat_codes), jnp.asarray(flat_ids), bs, r0,
        interpret=True, **kw)
    sq2, id2, st2 = ivf_scan_kernel(
        est, jnp.asarray(q), ws, wr, jnp.asarray(flat_rot),
        jnp.asarray(flat_codes), jnp.asarray(flat_ids), bs, r0,
        use_ref=True, **kw)
    assert np.array_equal(np.asarray(id1), np.asarray(id2))
    np.testing.assert_allclose(np.asarray(sq1), np.asarray(sq2),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(st1), np.asarray(st2), rtol=1e-6)

    # Replay the oracle with its trace and check the fetch decisions: a
    # tile with stage-1 survivors is always fetched, and the kernel's
    # per-tile DMA counters equal the trace's alive/need decisions.
    d_pad = flat_rot.shape[1]
    eps, scale, _, _ = block_table(est.table, d, block_d)
    qcodes, qscales = quantize_queries_block(
        jnp.asarray(np.pad(q, ((0, 0), (0, d_pad - d)))), block_d)
    cap_tiles = ivf_cap_tiles(max_bucket, block_c, starts_aligned=False)
    tile_offs = build_window_offsets(ws, wr, block_c=block_c,
                                     cap_tiles=cap_tiles, n_pad=n_pad)
    *_, trace = ivf_scan_ref(
        tile_offs, qcodes, jnp.asarray(np.pad(q, ((0, 0), (0, d_pad - d)))),
        qscales, r0, jnp.full((qn, k), jnp.inf),
        jnp.full((qn, k), -1, jnp.int32),
        jnp.asarray(flat_codes), jnp.asarray(flat_rot),
        jnp.asarray(flat_ids), bs, eps, scale, k=k, block_q=block_q,
        block_c=block_c, block_d=block_d, cap_tiles=cap_tiles,
        return_trace=True)
    st1 = np.asarray(st1)
    for i in range(q_tiles):
        recs = [r for r in trace if r["tile"] == i]
        for rec in recs:
            assert rec["fetched"] == (rec["alive"] > 0)  # no unsound elision
            assert (rec["slabs"] > 0) == (rec["alive"] > 0)
        slabs = sum(r["slabs"] for r in recs)
        s1f = sum(1 for r in recs if r["fresh"])
        assert st1[i * block_q, 4] == slabs
        assert st1[i * block_q, 5] == s1f


# ---- passed-parity vs the fp32 screen (no false prunes), wave by wave ------

def test_fused_passed_parity_vs_dco_screen(fused_idx, aniso_corpus, queries):
    """Replays every (tile, probe, ctile) wave of the fused scan through the
    oracle trace and asserts, against ``dco_screen_batch`` at the same
    frozen r², that (a) the fused ``passed`` set is identical and (b) no
    stage-1-pruned row ever passes the fp32 screen."""
    idx = fused_idx
    est = idx.estimator
    block_d = idx.scan_block_d
    block_q, block_c = 8, 128
    q_rot = est.rotate(jnp.asarray(queries))
    qn = q_rot.shape[0]
    assert qn % block_q == 0  # fixture: 24 queries -> 3 tiles

    cd = (jnp.sum(q_rot * q_rot, 1)[:, None]
          + jnp.sum(idx.centroids * idx.centroids, 1)[None, :]
          - 2.0 * q_rot @ idx.centroids.T)
    tile_cd = jnp.min(cd.reshape(qn // block_q, block_q, -1), axis=1)
    # 12 probes per tile (the index-level tests' n_probe): the far buckets
    # are where r² has tightened enough for whole waves to prune at stage 1.
    # With only the 4 nearest, elision depends on which tail tiles the
    # k-means clustering leaves a few rows in.
    _, tile_buckets = jax.lax.top_k(-tile_cd, 12)
    ws = idx.starts[tile_buckets]
    wr = idx.bucket_sizes[tile_buckets]
    n_pad = idx.flat_rot.shape[0]
    cap_tiles = ivf_cap_tiles(idx.max_bucket, block_c, starts_aligned=True)
    tile_offs = build_window_offsets(ws, wr, block_c=block_c,
                                     cap_tiles=cap_tiles, n_pad=n_pad)
    eps, scale, _, _ = block_table(est.table, q_rot.shape[1], block_d)
    qcodes, qscales = quantize_queries_block(q_rot, block_d)
    r0 = jnp.full((qn,), jnp.inf)

    *_, trace = ivf_scan_ref(
        tile_offs, qcodes, q_rot, qscales, r0, jnp.full((qn, 10), jnp.inf),
        jnp.full((qn, 10), -1, jnp.int32), idx.flat_codes, idx.flat_rot,
        idx.flat_ids, idx.bscales, eps, scale, k=10, block_q=block_q,
        block_c=block_c, block_d=block_d, cap_tiles=cap_tiles,
        return_trace=True)

    waves = pruned_rows = elided = 0
    for rec in trace:
        i = rec["tile"]
        qs = slice(i * block_q, (i + 1) * block_q)
        rows = idx.flat_rot[rec["row_start"]: rec["row_start"] + block_c]
        res = dco_screen_batch(q_rot[qs], rows, est.table,
                               jnp.asarray(rec["rsq"]))
        valid = np.asarray(rec["valid"])[None, :]
        ref_passed = np.asarray(res.passed) & valid
        fused_passed = np.asarray(rec["passed"]) & valid
        assert np.array_equal(fused_passed, ref_passed), (
            f"passed mismatch at tile={i} probe={rec['probe']} "
            f"ctile={rec['ctile']}")
        # no false prunes: stage-1 rejects are fp32 rejects
        s1_pruned = ~np.asarray(rec["active8"]) & valid
        assert not np.any(s1_pruned & ref_passed)
        # demand-paged fetch soundness: a wave with survivors is fetched;
        # an elided wave has no survivors, so no fp32 screen result is lost
        assert rec["fetched"] == (rec["alive"] > 0)
        if not rec["fetched"]:
            assert not np.any(ref_passed & ~s1_pruned & valid)
            elided += 1
        waves += 1
        pruned_rows += int(s1_pruned.sum())
    assert waves > 0 and pruned_rows > 0  # the prefilter does real work
    assert elided > 0  # demand paging elides real waves on this fixture


# ---- index-level behaviour -------------------------------------------------

def test_fused_search_matches_ref_and_recalls(fused_idx, aniso_corpus, queries):
    from repro.core import exact_knn

    _, gt = exact_knn(jnp.asarray(queries), jnp.asarray(aniso_corpus), 10)
    d1, i1, st = search_ivf_fused(fused_idx, jnp.asarray(queries), k=10,
                                  n_probe=12)
    d2, i2, _ = search_ivf_fused(fused_idx, jnp.asarray(queries), k=10,
                                 n_probe=12, use_ref=True)
    assert np.array_equal(np.asarray(i1), np.asarray(i2))
    # identical op graphs, but interpret-mode XLA may fuse differently than
    # the eager oracle — allow a few ULPs on the distances
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2),
                               rtol=5e-5, atol=1e-5)
    assert _recall(i1, gt) >= 0.9
    # distances ascending, no duplicate ids despite overlapping windows
    assert np.all(np.diff(np.asarray(d1), axis=1) >= -1e-5)
    for row in np.asarray(i1):
        real = row[row >= 0]
        assert len(set(real.tolist())) == len(real)
    # stage 1 carries most of the scan: int8 dims dominate fp32 dims
    assert st.avg_fp_dims < st.avg_int8_dims


def test_fused_requires_quant_build(aniso_corpus, queries):
    idx = build_ivf(aniso_corpus, n_clusters=16, delta_d=16)
    with pytest.raises(ValueError, match="quant"):
        search_ivf_fused(idx, jnp.asarray(queries), k=5)


def test_fused_search_reports_fetch_elision(fused_idx, queries):
    """The index-level stats surface the demand-paged accounting: a real
    skip rate, slab counts consistent with their totals, and DMA-granular
    fetched bytes that respond to the elision."""
    _, _, st = search_ivf_fused(fused_idx, jnp.asarray(queries), k=10,
                                n_probe=12)
    assert st.s1_tiles_fetched > 0
    d_pad = fused_idx.flat_rot.shape[1]
    assert st.s2_slabs_total == st.s1_tiles_fetched * (
        d_pad // fused_idx.scan_block_d)
    assert 0 < st.s2_slabs_fetched < st.s2_slabs_total
    assert 0.0 < st.s2_skip_rate < 1.0
    assert st.fetched_bytes_per_query > 0
    # consistency with the canonical accounting helpers
    from repro.quant.accounting import stage2_skip_rate

    assert st.s2_skip_rate == pytest.approx(
        stage2_skip_rate(st.s2_slabs_fetched, st.s2_slabs_total))


def test_fused_seeding_saves_bytes(fused_idx, queries):
    _, i_seed, st_seed = search_ivf_fused(fused_idx, jnp.asarray(queries),
                                          k=10, n_probe=8, seed_r=True)
    _, i_no, st_no = search_ivf_fused(fused_idx, jnp.asarray(queries),
                                      k=10, n_probe=8, seed_r=False)
    assert st_seed.bytes_per_query <= st_no.bytes_per_query
    assert _recall(i_seed, np.asarray(i_no)) >= 0.9  # same result set


# ---- quantized threshold seeding (satellite) on the classic paths ----------

def test_search_ivf_seed_r_prunes_earlier(fused_idx, aniso_corpus, queries):
    from repro.core import exact_knn

    _, gt = exact_knn(jnp.asarray(queries), jnp.asarray(aniso_corpus), 10)
    d0, i0, a0 = search_ivf(fused_idx, jnp.asarray(queries), k=10, n_probe=8,
                            use_quant=True)
    d1, i1, a1 = search_ivf(fused_idx, jnp.asarray(queries), k=10, n_probe=8,
                            use_quant=True, seed_r=True)
    assert _recall(i1, gt) >= _recall(i0, gt) - 0.02
    assert float(a1) <= float(a0)  # wave 0 already prunes


def test_search_ivf_seed_r_needs_quant(aniso_corpus, queries):
    idx = build_ivf(aniso_corpus, n_clusters=16, delta_d=16)
    with pytest.raises(ValueError, match="seed_r"):
        search_ivf(idx, jnp.asarray(queries), k=10, seed_r=True)


def test_search_graph_seed_r(aniso_corpus, queries):
    from repro.core import exact_knn
    from repro.index.graph import build_graph, search_graph

    sub = np.asarray(aniso_corpus)[:1200]
    g = build_graph(sub, m=12, ef_construction=48, delta_d=16, quant="int8")
    _, gt = exact_knn(jnp.asarray(queries), jnp.asarray(sub), 10)
    d0, i0, a0 = search_graph(g, jnp.asarray(queries), k=10, ef=48)
    d1, i1, a1 = search_graph(g, jnp.asarray(queries), k=10, ef=48,
                              seed_r=True)
    assert _recall(i1, gt) >= _recall(i0, gt) - 0.02
    for row in np.asarray(i1):  # seeds must not duplicate walked nodes
        real = row[row >= 0]
        assert len(set(real.tolist())) == len(real)


# ---- autotuned refine budget (satellite) -----------------------------------

def test_autotune_refine_budget_tracks_band_width():
    from repro.launch.annservice import autotune_refine_budget

    rng = np.random.default_rng(0)
    sample = rng.standard_normal((512, 32)).astype(np.float32)
    tight = jnp.full((32,), 1e-4, jnp.float32)
    coarse = jnp.full((32,), 0.3, jnp.float32)
    b_tight, d_tight = autotune_refine_budget(tight, sample, k=10, wave=1024)
    b_coarse, d_coarse = autotune_refine_budget(coarse, sample, k=10, wave=1024)
    assert 10 <= b_tight <= b_coarse <= 1024
    assert d_tight["band_width"] < d_coarse["band_width"]
    # near-exact codes need (almost) no slack beyond k itself
    assert b_tight <= 12


# ---- the flat route's tile rule --------------------------------------------

@pytest.mark.parametrize("q,wave,d_pad,dtype,interpret", [
    (64, 4096, 256, jnp.bfloat16, False),   # the serving batch
    (96, 4096, 256, jnp.bfloat16, False),   # 96 % 32 == 0: one 96-row tile
    (16, 1024, 64, jnp.float32, True),      # interpret-mode tests
    (64, 4096, 1024, jnp.float32, False),   # wide rows narrow the tile
    (1024, 8192, 256, jnp.bfloat16, False),  # a tall batch narrows it too
    (4096, 8192, 256, jnp.bfloat16, False),  # too tall: 1024-row tiles
])
def test_flat_tile_shape_rule(q, wave, d_pad, dtype, interpret):
    from repro.kernels.ivf_scan import VMEM_LIMIT_BYTES, vmem_bytes
    from repro.kernels.ops import (
        FLAT_BLOCK_C, auto_block_q, flat_tile_shape)

    block_q, block_c = flat_tile_shape(q, wave, d_pad, dtype,
                                       interpret=interpret)
    assert q % block_q == 0 and block_q % auto_block_q(interpret) == 0
    assert wave % block_c == 0 and block_c in FLAT_BLOCK_C
    assert vmem_bytes(block_q, block_c, d_pad, dtype) <= VMEM_LIMIT_BYTES
    # the widest width that fits beside this query tile
    wider = [bc for bc in FLAT_BLOCK_C if bc > block_c and wave % bc == 0]
    assert all(vmem_bytes(block_q, bc, d_pad, dtype) > VMEM_LIMIT_BYTES
               for bc in wider)
    if q % auto_block_q(interpret) == 0 and vmem_bytes(
            q, FLAT_BLOCK_C[-1], d_pad, dtype) <= VMEM_LIMIT_BYTES:
        assert block_q == q


def test_flat_tile_shape_edges():
    import inspect

    from repro.kernels.ops import (
        auto_block_q, flat_tile_shape, graph_scan_kernel)

    assert flat_tile_shape(64, 128, 256, jnp.bfloat16) == (64, 128)
    assert flat_tile_shape(64, 4096 + 128, 256, jnp.bfloat16)[1] == 128
    with pytest.raises(ValueError, match="query tile"):
        flat_tile_shape(48 + 8, 4096, 256, jnp.bfloat16)  # 56: no 32-multiple
    with pytest.raises(ValueError, match="wave"):
        flat_tile_shape(64, 4096 + 64, 256, jnp.bfloat16)
    # the other routes keep their own tiles
    assert (auto_block_q(True), auto_block_q(False)) == (8, 32)
    ivf = inspect.signature(ivf_scan_kernel).parameters
    assert (ivf["block_q"].default, ivf["block_c"].default) == (32, 128)
    graph = inspect.signature(graph_scan_kernel).parameters
    assert (graph["block_q"].default, graph["block_c"].default) == (8, 32)


def test_flat_fused_step_wide_tile_matches_narrow(monkeypatch):
    """The flat fused step at the rule's shape returns the ids and
    distances it returns at the IVF route's (query tile, 128) shape, in
    fewer int8 tile fetches, with recall against exact search no lower."""
    from jax.sharding import Mesh

    from repro.configs.dade_ivf import ServiceConfig
    from repro.core import exact_knn
    from repro.data.pipeline import synthetic_queries, synthetic_vectors
    from repro.kernels import ops
    from repro.launch.annservice import build_search_step

    svc = ServiceConfig(corpus_per_device=8192, dim=64, query_batch=16, k=10,
                        delta_d=32, wave=4096, quant="int8")
    corpus = synthetic_vectors(svc.corpus_per_device, svc.dim, seed=0,
                               decay=0.05)
    queries = synthetic_queries(svc.query_batch, svc.dim, corpus, seed=1)
    est = build_estimator("dade", corpus[:4000], jax.random.PRNGKey(0),
                          p_s=svc.p_s, delta_d=svc.delta_d)
    eps, scale, d_pad, eps_lo = block_table(est.table, svc.dim, svc.delta_d)
    c_rot = jnp.pad(est.rotate(jnp.asarray(corpus)),
                    ((0, 0), (0, d_pad - svc.dim)))
    q_rot = jnp.pad(est.rotate(jnp.asarray(queries)),
                    ((0, 0), (0, d_pad - svc.dim)))
    bs = fit_block_scales(c_rot, svc.delta_d)
    codes = quantize_block(c_rot, bs, svc.delta_d)
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))

    def run(shape=None):
        if shape is not None:
            monkeypatch.setattr(ops, "flat_tile_shape", lambda *a, **k: shape)
        step = build_search_step(svc, mesh, quant="int8", fused=True,
                                 with_stats=True, interpret=True)
        return [np.asarray(x) for x in
                jax.jit(step)(c_rot, codes, bs, q_rot, eps, scale, eps_lo)]

    wide = ops.flat_tile_shape(svc.query_batch, svc.wave, d_pad, c_rot.dtype,
                               interpret=True)
    assert wide == (16, 2048)
    d_w, i_w, st_w = run()
    d_n, i_n, st_n = run((ops.auto_block_q(True), 128))
    assert np.array_equal(i_w, i_n)
    np.testing.assert_allclose(d_w, d_n, rtol=1e-6, atol=1e-6)
    _, gt = exact_knn(jnp.asarray(queries), jnp.asarray(corpus), svc.k)
    assert _recall(i_w, gt) >= _recall(i_n, gt) >= 0.9
    # int8 tiles DMA'd: one 16-row query tile over 2048-row tiles against
    # two 8-row tiles over 128-row tiles
    n_tiles = svc.corpus_per_device
    assert st_w[5] == n_tiles // 2048 and st_n[5] == 2 * n_tiles // 128
    # the step returns every STATS_COLS column; both tile shapes merged
    assert st_w.shape == st_n.shape == (len(STATS_COLS),)
    assert st_w[6] > 0 and st_n[6] > 0
    assert st_w[2] == st_n[2] == svc.query_batch * svc.corpus_per_device
