"""The paper's own production workload: pod-scale DADE vector search.

The corpus (rotated into the PCA basis at ingest) is sharded row-wise over
*every* mesh axis; each device screens its shard with the blocked DADE DCO
(same block semantics as the Pallas kernel), local top-K results then merge
through a hierarchical all-gather tree (payload per hop: Q×K, not
devices×Q×K).  A two-phase threshold seed (cheap first-block estimate +
one small all-reduce) gives every shard a tight r before the full screen —
the distributed analogue of the paper's warm max-heap.

``quant="int8"`` (repro.quant) swaps the wave scan onto the int8-encoded
corpus: each wave streams 1 byte/dim, tests the sound distance lower bound
against the running k-th threshold, and only a fixed per-wave budget of
bound-qualified candidates touches the fp corpus for exact refinement.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.dade_ivf import ServiceConfig
from repro.core.estimators import SEED_SLACK, first_enabled_eps
from repro.launch.mesh import shard_map
from repro.obs.trace import current_tracer
from repro.quant.scalar import cum_err_sq
from repro.distributed.collectives import hierarchical_topk

__all__ = ["build_search_step", "build_graph_engine",
           "build_sharded_graph_engine", "search_input_specs",
           "autotune_refine_budget",
           "ContinuousGraphEngine", "ContinuousIVFEngine", "RetiredQuery",
           "SLOPolicy", "parse_slo", "slo_effort", "slo_signal"]


def autotune_refine_budget(scales, sample_rot, *, k: int, wave: int,
                           num_queries: int = 32, safety: float = 1.5):
    """Derive the per-wave exact-refine budget from the stage-1 band width.

    The quantized wave scan admits to exact refinement every row whose
    *lower bound* beats the running k-th distance r.  Rows that qualify but
    lose are exactly those inside the bound band: d <= r + 2E(D), where
    2E(D) is the upper-minus-lower bound width at full dimension (see
    ``repro.quant.scalar``).  So the right budget is k (true entrants) plus
    the expected number of in-band rows per wave — a data quantity, not a
    constant.  Estimated here on a corpus sample with corpus rows as
    pseudo-queries (offline, numpy): for each pseudo-query take its k-th
    sample distance r̂ and count rows with d <= r̂ + 2E.

    Returns (budget int in [k, wave], diagnostics dict with ``band_width``
    (2E(D)) and ``in_band_frac``).
    """
    import numpy as np

    sample = np.asarray(sample_rot, np.float32)
    n = sample.shape[0]
    scales = jnp.asarray(scales, jnp.float32)
    e_band = float(jnp.sqrt(cum_err_sq(scales, jnp.asarray([scales.shape[0]]))[0]))
    nq = min(num_queries, n)
    qs = sample[:: max(n // nq, 1)][:nq]
    d = np.sqrt(np.maximum(
        np.sum(qs * qs, 1)[:, None] + np.sum(sample * sample, 1)[None, :]
        - 2.0 * qs @ sample.T, 0.0))
    kth = np.partition(d, k, axis=1)[:, k]  # k-th excluding self (d=0)
    in_band = np.mean(d <= (kth[:, None] + 2.0 * e_band)) - (k + 1) / n
    in_band = max(float(in_band), 0.0)
    budget = int(np.clip(k + np.ceil(in_band * wave * safety), k, wave))
    return budget, {"band_width": 2.0 * e_band, "in_band_frac": in_band}


def build_graph_engine(index, *, k: int, ef: int = 48, expand: int = 2,
                       block_q: int | None = None, seed_r: bool = False,
                       with_stats: bool = False,
                       interpret: bool | None = None):
    """Serving engine for the ``--index graph`` route.

    Wraps the batched beam-scan megakernel (``index.graph
    .search_graph_fused``) behind the scheduler-shaped step the serving
    driver expects: ``step(batch_np) -> (dists, ids[, GraphScanStats])``
    as numpy arrays.  The graph walk is wave-synchronous with host-driven
    frontier selection, so — unlike the flat/IVF routes — it is not a
    single shard_mapped jit step: this engine runs the whole corpus per
    replica and the batcher amortizes launches across requests (queries
    shard trivially across replicas).  To shard the *corpus* of the walk
    across a mesh use ``build_sharded_graph_engine`` instead.
    ``interpret=None`` takes ``ops.auto_interpret()``; ``block_q`` defaults
    to ``ops.auto_block_q`` of that mode.
    """
    from repro.index.graph import search_graph_fused
    from repro.kernels.ops import auto_block_q, auto_interpret

    import numpy as np

    if interpret is None:
        interpret = auto_interpret()
    if block_q is None:
        block_q = auto_block_q(interpret)

    def step(batch_np):
        # current_tracer() resolves at CALL time, so a tracer serve.py
        # installs after engine build is still seen (NULL_TRACER: no-op).
        with current_tracer().span("engine.step", route="graph",
                                   batch=len(batch_np)):
            d, i, st = search_graph_fused(
                index, jnp.asarray(batch_np), k=k, ef=ef, expand=expand,
                block_q=block_q, seed_r=seed_r, interpret=interpret)
        if with_stats:
            return np.asarray(d), np.asarray(i), st
        return np.asarray(d), np.asarray(i)

    return step


def build_sharded_graph_engine(index, mesh, *, k: int, ef: int = 48,
                               expand: int = 2, block_q: int | None = None,
                               seed_r: bool = False, decoupled: bool = True,
                               route_mult: float = 1.0, max_waves: int = 64,
                               with_stats: bool = False,
                               interpret: bool | None = None):
    """Corpus-sharded serving engine for ``--index graph --graph-shards N``.

    The mesh-backed realization of ``index.graph.search_graph_sharded``:
    the adjacency-flat slab is row-sharded over the mesh's single axis
    (every shard owns a contiguous node range — the device sharding
    boundary lands on node boundaries by ``shard_graph_nodes``'s
    construction), and each frontier wave is ONE ``shard_map``'d jit step:
    every shard runs the beam-scan megakernel over its local slab with the
    wave-start threshold frozen, then the per-query beam windows, visited
    bitmaps, and per-shard stats are ``all_gather``'d along the mesh axis
    and merged in-step (``merge_shard_windows`` — the same jnp arithmetic
    the host-simulated driver uses, so the two paths return identical
    results and either is bit-identical to the ``num_shards=1, use_ref``
    single-host beam oracle).  The host drives waves and frontier
    selection exactly as in the single-replica engine.

    Failover: every ``step`` call consults the chaos harness
    (``runtime.chaos.current_chaos()`` — the null object when no drill is
    armed, so the healthy path is branch-free and bit-identical to pre-PR
    behaviour).  Shards reported dead get their node ranges tombstoned via
    ``search_graph_sharded(tombstones=...)``: the dead device still sits in
    the ``shard_map`` step (the wave is a collective — a real deployment
    would re-mesh; this simulation keeps the mesh and starves the shard)
    but its frontier offsets are all -1, so it screens nothing and
    contributes only the carried-in window, the merge identity.  Surviving
    shards keep serving, bit-identical to the surviving-corpus oracle
    (``num_shards=1, use_ref=True`` with the same tombstones).

    Fails fast, naming the offending value, on a multi-axis mesh or a node
    count the mesh size does not divide.  Returns
    ``step(batch_np) -> (dists, ids[, GraphShardedStats])``.
    """
    import numpy as np

    from repro.index.graph import (
        dead_shard_tombstones, merge_shard_windows, search_graph_sharded,
        shard_graph_nodes,
    )
    from repro.kernels.ops import (
        auto_block_q, auto_interpret, graph_scan_kernel)
    from repro.runtime.chaos import current_chaos

    axes = tuple(mesh.axis_names)
    if len(axes) != 1:
        raise ValueError(
            f"sharded graph serving needs a 1-D mesh (one shard axis), got "
            f"axes={axes}")
    ax = axes[0]
    num_shards = int(mesh.devices.size)
    n = index.corpus_rot.shape[0]
    shard_graph_nodes(n, num_shards)  # fail-fast divisibility check
    per = n // num_shards
    if not index.has_fused:
        raise ValueError(
            "sharded graph serving needs build_graph(..., quant='int8')")
    if interpret is None:
        interpret = auto_interpret()
    if block_q is None:
        block_q = auto_block_q(interpret)
    thresh_col = (k - 1) if decoupled else (ef - 1)
    a_block = index.adj_block
    block_d = index.scan_block_d
    est = index.estimator
    gscales = index.gscales

    row_shard = NamedSharding(mesh, P(axes, None))
    adj_rot = jax.device_put(index.adj_rot, row_shard)
    adj_codes = jax.device_put(index.adj_codes, row_shard)
    adj_ids = jax.device_put(index.adj_ids, NamedSharding(mesh, P(axes)))

    def local_wave(offs_s, q_sorted, top_sq, top_ids, r0, vis,
                   a_rot, a_codes, a_ids):
        base = jax.lax.axis_index(ax) * per
        sq, ids_, st, vis_out = graph_scan_kernel(
            est, q_sorted, offs_s[0], top_sq, top_ids, r0,
            a_rot, a_codes, a_ids, gscales, vis,
            vis_base=base, vis_nodes=n, ef=ef, thresh_col=thresh_col,
            block_q=block_q, block_c=a_block, block_d=block_d,
            tighten=False, interpret=interpret)
        # Cross-shard frontier exchange: windows / bitmaps / stats ride one
        # all-gather per wave (the exchange ledger prices it), merged with
        # the same arithmetic as the host-simulated driver.
        g_sq = jax.lax.all_gather(sq, ax)
        g_ids = jax.lax.all_gather(ids_, ax)
        g_vis = jax.lax.all_gather(vis_out, ax)
        g_st = jax.lax.all_gather(st, ax)
        m_sq, m_ids = merge_shard_windows(g_sq, g_ids, ef=ef)
        m_vis = g_vis[0]
        for s in range(1, num_shards):
            m_vis = m_vis | g_vis[s]
        return m_sq, m_ids, m_vis, g_st

    step_fn = jax.jit(shard_map(
        local_wave,
        mesh=mesh,
        in_specs=(P(ax, None, None), P(), P(), P(), P(), P(),
                  P(ax, None), P(ax, None), P(ax)),
        out_specs=(P(), P(), P(), P()),
        check_vma=False,
    ))

    def wave_step(offs_sh, q_sorted, top_sq, top_ids, r0, vis):
        return step_fn(
            jnp.asarray(offs_sh), jnp.asarray(q_sorted),
            jnp.asarray(top_sq), jnp.asarray(top_ids), jnp.asarray(r0),
            jnp.asarray(vis), adj_rot, adj_codes, adj_ids)

    def step(batch_np):
        dead = current_chaos().dead_shards(num_shards)
        tombs = dead_shard_tombstones(n, num_shards, dead) if dead else ()
        with current_tracer().span("engine.step", route="graph-sharded",
                                   shards=num_shards, batch=len(batch_np),
                                   dead_shards=len(dead)):
            d, i, st = search_graph_sharded(
                index, jnp.asarray(batch_np), num_shards=num_shards, k=k,
                ef=ef, expand=expand, block_q=block_q, max_waves=max_waves,
                seed_r=seed_r, decoupled=decoupled, route_mult=route_mult,
                wave_step=wave_step, tombstones=tombs)
        if with_stats:
            return np.asarray(d), np.asarray(i), st
        return np.asarray(d), np.asarray(i)

    return step


def _pad_dim(d: int, block: int) -> int:
    return (d + block - 1) // block * block


def search_input_specs(svc: ServiceConfig, mesh, *, quant: str | None = None,
                       fused: bool = False):
    """ShapeDtypeStructs + shardings for the search step.

    ``quant="int8"`` inserts (corpus_q int8, qscales f32) after the fp
    corpus: codes are sharded row-wise exactly like the corpus (every wave
    streams them), scales are replicated.  ``fused`` switches the code
    layout to the megakernel's per-*block* quantization: one scale per
    Δd-dim block (shape (s_steps,)) instead of one per dimension.
    """
    n_dev = mesh.devices.size
    d_pad = _pad_dim(svc.dim, svc.delta_d)
    s_steps = d_pad // svc.delta_d
    dt = jnp.dtype(svc.dtype)
    corpus = jax.ShapeDtypeStruct((n_dev * svc.corpus_per_device, d_pad), dt)
    queries = jax.ShapeDtypeStruct((svc.query_batch, d_pad), dt)
    eps = jax.ShapeDtypeStruct((s_steps,), jnp.float32)
    scale = jax.ShapeDtypeStruct((s_steps,), jnp.float32)
    eps_lo = jax.ShapeDtypeStruct((s_steps,), jnp.float32)
    axes = tuple(mesh.axis_names)
    row_shard = NamedSharding(mesh, P(axes, None))
    repl = NamedSharding(mesh, P())
    if quant == "int8":
        corpus_q = jax.ShapeDtypeStruct(corpus.shape, jnp.int8)
        qscales = jax.ShapeDtypeStruct(
            (s_steps,) if fused else (d_pad,), jnp.float32)
        return (
            (corpus, corpus_q, qscales, queries, eps, scale, eps_lo),
            (row_shard, row_shard, repl, repl, repl, repl, repl),
        )
    return (
        (corpus, queries, eps, scale, eps_lo),
        (row_shard, repl, repl, repl, repl),
    )


def build_search_step(svc: ServiceConfig, mesh, *, two_phase: bool = True,
                      seed_waves: int = 1, quant: str | None = None,
                      refine_per_wave: int | None = None,
                      fused: bool | None = None,
                      with_stats: bool = False,
                      interpret: bool | None = None):
    """Returns search_step(corpus_rot, queries_rot, eps, scale, eps_lo)
    -> (dists, ids); with ``quant="int8"``:
    search_step(corpus_rot, corpus_q, qscales, queries_rot, eps, scale,
    eps_lo) -> (dists, ids).

    Quantized mode (repro.quant): every wave streams the *int8* corpus
    (1 byte/dim of HBM traffic instead of 2-4) and computes the sound
    lower bound of each distance; only the best ``refine_per_wave``
    candidates per wave (those whose bound beats the current threshold)
    touch the fp corpus for exact refinement.  Rows whose lower bound
    exceeds the running k-th distance provably cannot enter the top-K, so
    the only recall exposure is the refine budget — which the serving
    driver autotunes from the stage-1 band width
    (``autotune_refine_budget``); 2k is only the blind fallback when no
    corpus sample is available.

    ``fused`` routes the quantized wave scan through the fused wave-scan
    megakernel (``repro.kernels.ivf_scan``): each wave is one bucket
    window, the int8 stage is a true int8×int8 MXU product over
    *block*-quantized codes (the corpus must then be encoded with
    ``quantize_block`` and ``qscales`` carries one scale per Δd block),
    survivors re-screen through the blockwise DADE schedule in-kernel, and
    the local top-K / threshold stay in VMEM across waves.  Default
    (None): the megakernel where it compiles, the jnp wave scan in
    interpret mode (correct but slow there, so tests opt in explicitly).

    ``interpret`` is the megakernel's mode; None takes
    ``ops.auto_interpret()`` (compiled on TPU).  The fused route's tile
    shape follows from the step's shapes and that mode
    (``ops.flat_tile_shape``).

    ``with_stats`` (fused route only) appends a third output: a replicated
    (7,) f32 vector of the megakernel's scan counters summed over shards
    and queries (``repro.kernels.ivf_scan.STATS_COLS`` order) — the serving
    driver turns columns 4-5 into the fetched-vs-skipped stage-2 byte
    report per wave and feeds column 6, the merge's rounds, to
    ``ivf.fused.merge_rounds``.
    """
    from repro.kernels.ops import auto_interpret, flat_tile_shape

    axes = tuple(mesh.axis_names)
    k = svc.k
    wave = svc.wave
    block_d = svc.delta_d
    if interpret is None:
        interpret = auto_interpret()
    if fused is None:
        fused = not interpret
    if refine_per_wave is None:
        refine_per_wave = getattr(svc, "refine_per_wave", 0) or 2 * k
    refine_per_wave = min(refine_per_wave, wave)

    # jax.lax.axis_size is a recent addition; mesh shape is static anyway.
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def shard_base(n_local):
        """Global row id offset for this shard (inside shard_map)."""
        lin = jnp.zeros((), jnp.int32)
        stride = 1
        for ax in reversed(axes):
            lin = lin + jax.lax.axis_index(ax) * stride
            stride = stride * axis_sizes[ax]
        return lin.astype(jnp.int32) * n_local

    def seed_rsq(corpus, queries, eps):
        """Two-phase threshold seed (exact-verified local top-k, pmin)."""
        qb = queries[:, :block_d]
        cb = corpus[: seed_waves * wave, :block_d]
        est0 = (
            jnp.sum(qb * qb, 1)[:, None]
            + jnp.sum(cb * cb, 1)[None, :]
            - 2.0 * jnp.matmul(qb, cb.T, precision=jax.lax.Precision.HIGHEST)
        )
        _, idx = jax.lax.top_k(-est0, k)
        sample = corpus[: seed_waves * wave]
        cand = jnp.take(sample, idx.reshape(-1), axis=0).reshape(
            idx.shape[0], idx.shape[1], -1)
        diff = (cand - queries[:, None, :]).astype(jnp.float32)
        exact_sq = jnp.sum(diff * diff, axis=-1)
        kth_local = jnp.max(exact_sq, axis=1)
        r0 = kth_local
        with jax.named_scope("exchange.seed"):
            for ax in axes:
                r0 = jax.lax.pmin(r0, ax)
        # Widen by the first ENABLED checkpoint's overshoot band: a
        # blocked schedule whose early checkpoints are disabled (the
        # EPS_DISABLED sentinel — fdscanning under a small block_d) must
        # seed from the first epsilon that actually screens, not ~1e19.
        # SEED_SLACK keeps the zero-widening case sound under float
        # reassociation (see core.estimators).
        return (r0 * (1.0 + first_enabled_eps(eps)) ** 2
                * (1.0 + SEED_SLACK))

    def merge_shards(top_sq, top_ids):
        """Hierarchical cross-shard top-K merge (innermost axis first:
        cheapest links carry the most traffic at TPU topology
        granularity), named ``exchange.merge`` in every body."""
        with jax.named_scope("exchange.merge"):
            return hierarchical_topk(top_sq, top_ids, tuple(reversed(axes)), k)

    def local_search(corpus, queries, eps, scale, eps_lo):
        """Per-shard screen. corpus: (N_local, D). Runs inside shard_map."""
        n_local, dim = corpus.shape
        q = queries.shape[0]

        base = shard_base(n_local)

        # Phase 1: cheap first-block estimate seeds the threshold globally.
        # §Perf iteration A2: seed from the first `seed_waves` waves only —
        # the k-th best of a corpus SAMPLE still upper-bounds the global
        # k-th (safe, slightly looser), and the (Q, N_local) phase-1 blob
        # (4 GiB at 1M rows/device) shrinks to (Q, wave).  (Exact-verified
        # local top-k + pmin; widened by the first-checkpoint overshoot
        # band so a true neighbor whose estimate overshoots is admitted.)
        if two_phase:
            r_sq = seed_rsq(corpus, queries, eps)
        else:
            r_sq = jnp.full((q,), jnp.inf)

        # Phase 2: wave screen with the blocked DADE DCO.
        num_waves = n_local // wave
        corpus_w = corpus.reshape(num_waves, wave, dim)

        s_steps = dim // block_d
        qn = queries.shape[0]
        # per-block query norms, shared across waves
        qn_blk = jnp.sum(
            (queries * queries).astype(jnp.float32)
            .reshape(qn, s_steps, block_d), axis=2)  # (Q, S)

        def screen(rows, r_sq):
            """§Perf iteration A3: block-incremental screen carrying only
            (Q, C) state through a fori loop — dade_dco_ref's materialized
            (S, Q, C) cumsum stack costs ~3x the HBM traffic.  Semantics are
            identical for `passed` and survivor distances (same checkpoints
            and thresholds)."""
            cn_blk = jnp.sum(
                (rows * rows).astype(jnp.float32)
                .reshape(rows.shape[0], s_steps, block_d), axis=2)  # (C, S)

            def body_s(st, carry):
                psum, retired = carry
                qb = jax.lax.dynamic_slice_in_dim(queries, st * block_d, block_d, 1)
                cb = jax.lax.dynamic_slice_in_dim(rows, st * block_d, block_d, 1)
                dot = jax.lax.dot_general(
                    qb, cb, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                    precision=jax.lax.Precision.HIGHEST)
                blk = qn_blk[:, st, None] + cn_blk[None, :, st] - 2.0 * dot
                psum = psum + jnp.maximum(blk, 0.0)
                est = psum * scale[st]
                thresh = (1.0 + eps[st]) ** 2 * r_sq[:, None]
                retired = jnp.logical_or(
                    retired, jnp.logical_and(est > thresh, st < s_steps - 1))
                return psum, retired

            psum0 = jnp.zeros((qn, rows.shape[0]), jnp.float32)
            retired0 = jnp.zeros((qn, rows.shape[0]), bool)
            psum, retired = jax.lax.fori_loop(
                0, s_steps, body_s, (psum0, retired0))
            passed = jnp.logical_and(~retired, psum <= r_sq[:, None])
            return psum, passed

        def body(carry, xs):
            top_sq, top_ids, r_sq = carry
            rows, wbase = xs
            est_sq, passed = screen(rows, r_sq)
            ids = (base + wbase + jnp.arange(wave, dtype=jnp.int32))[None, :]
            new_sq = jnp.where(passed, est_sq, jnp.inf)
            all_sq = jnp.concatenate([top_sq, new_sq], 1)
            all_ids = jnp.concatenate(
                [top_ids, jnp.broadcast_to(ids, new_sq.shape)], 1)
            neg, idx = jax.lax.top_k(-all_sq, k)
            top_sq = -neg
            top_ids = jnp.take_along_axis(all_ids, idx, axis=1)
            r_sq = jnp.minimum(r_sq, top_sq[:, -1])
            return (top_sq, top_ids, r_sq), None

        init = (
            jnp.full((q, k), jnp.inf),
            jnp.full((q, k), -1, jnp.int32),
            r_sq,
        )
        bases = jnp.arange(num_waves, dtype=jnp.int32) * wave
        (top_sq, top_ids, _), _ = jax.lax.scan(body, init, (corpus_w, bases))

        top_sq, top_ids = merge_shards(top_sq, top_ids)
        return jnp.sqrt(jnp.maximum(top_sq, 0.0)), top_ids

    def local_search_quant(corpus, codes, scales, queries, eps, scale, eps_lo):
        """Quantized per-shard scan: int8 wave stream + budgeted fp refine.

        corpus: (N_local, D) fp/bf16 (refine source, touched sparsely);
        codes: (N_local, D) int8; scales: (D,) replicated.
        """
        n_local, dim = corpus.shape
        q = queries.shape[0]
        base = shard_base(n_local)

        if two_phase:
            r_sq = seed_rsq(corpus, queries, eps)
        else:
            r_sq = jnp.full((q,), jnp.inf)

        # Full-D quantization error band E(D): the wave scan tests the
        # full-dimension lower bound once per row instead of the blockwise
        # schedule — XLA computes every block regardless, and one fused
        # (Q, wave) matmul over int8-sourced operands is the
        # bandwidth-optimal shape here.
        dim_arr = jnp.asarray([scales.shape[0]])
        e_band = jnp.sqrt(cum_err_sq(scales, dim_arr)[0])

        qf = queries.astype(jnp.float32)
        qn = jnp.sum(qf * qf, axis=1)[:, None]  # (Q, 1)

        num_waves = n_local // wave
        corpus_w = corpus.reshape(num_waves, wave, dim)
        codes_w = codes.reshape(num_waves, wave, dim)

        def body(carry, xs):
            top_sq, top_ids, r_sq = carry
            rows_fp, rows_q, wbase = xs
            cf = rows_q.astype(jnp.float32) * scales[None, :]  # (W, D)
            dot = jax.lax.dot_general(
                qf, cf, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST)
            cn = jnp.sum(cf * cf, axis=1)[None, :]
            dstq = jnp.maximum(qn + cn - 2.0 * dot, 0.0)  # (Q, W) dequant dist
            lb = jnp.maximum(jnp.sqrt(dstq) - e_band, 0.0) ** 2 * (1.0 - 1e-4)
            # Rows whose lower bound beats r are the only possible top-K
            # entrants; refine the best `refine_per_wave` of them exactly.
            cand = jnp.where(lb <= r_sq[:, None], lb, jnp.inf)
            _, idx = jax.lax.top_k(-cand, refine_per_wave)  # (Q, R)
            gathered = jnp.take(rows_fp, idx.reshape(-1), axis=0).reshape(
                q, refine_per_wave, dim)
            diff = (gathered - queries[:, None, :]).astype(jnp.float32)
            exact_sq = jnp.sum(diff * diff, axis=-1)  # (Q, R)
            # Over-budget rows (selected slots holding inf bounds) carry
            # exact > r and fall out of the merge naturally.
            ids = base + wbase + idx.astype(jnp.int32)
            all_sq = jnp.concatenate([top_sq, exact_sq], 1)
            all_ids = jnp.concatenate([top_ids, ids], 1)
            neg, sel = jax.lax.top_k(-all_sq, k)
            top_sq = -neg
            top_ids = jnp.take_along_axis(all_ids, sel, axis=1)
            r_sq = jnp.minimum(r_sq, top_sq[:, -1])
            return (top_sq, top_ids, r_sq), None

        init = (
            jnp.full((q, k), jnp.inf),
            jnp.full((q, k), -1, jnp.int32),
            r_sq,
        )
        bases = jnp.arange(num_waves, dtype=jnp.int32) * wave
        (top_sq, top_ids, _), _ = jax.lax.scan(
            body, init, (corpus_w, codes_w, bases))

        top_sq, top_ids = merge_shards(top_sq, top_ids)
        return jnp.sqrt(jnp.maximum(top_sq, 0.0)), top_ids

    def local_search_quant_fused(corpus, codes, bscales, queries, eps, scale,
                                 eps_lo):
        """Quantized per-shard scan through the fused megakernel.

        Every wave is one bucket window of the flat shard; the kernel runs
        the int8×int8 MXU prefilter + blockwise fp32 DADE re-screen and
        carries the local top-K / threshold r² in VMEM across waves.
        codes: (N_local, D) int8 *block*-quantized; bscales: (S,).
        """
        from repro.kernels.ivf_scan import ivf_scan_kernel_call
        from repro.quant.scalar import quantize_queries_block

        n_local, dim = corpus.shape
        q = queries.shape[0]
        base = shard_base(n_local)
        if wave % 128 or n_local % wave:
            raise ValueError("fused scan needs wave % 128 == 0 and "
                             "corpus_per_device % wave == 0")
        block_q, block_c = flat_tile_shape(q, wave, dim, corpus.dtype,
                                           interpret=interpret)
        if not interpret and block_d % 128:
            raise ValueError(
                f"compiled fused serving needs delta_d % 128 == 0 "
                f"(demand-paged stage-2 slab DMA lands lane-aligned), got "
                f"{block_d}; "
                f"configure ServiceConfig(delta_d=128) or route "
                f"fused=False")

        r0 = seed_rsq(corpus, queries, eps) if two_phase else jnp.full(
            (q,), jnp.inf)
        qf = queries.astype(jnp.float32)
        qcodes, qscales = quantize_queries_block(qf, block_d)
        q_tiles = q // block_q
        num_waves = n_local // wave
        cap_tiles = wave // block_c
        base_tiles = jnp.arange(num_waves, dtype=jnp.int32) * cap_tiles
        t_idx = jnp.arange(cap_tiles, dtype=jnp.int32)
        offs = jnp.broadcast_to(
            (base_tiles[None, :, None] + t_idx[None, None, :]),
            (q_tiles, num_waves, cap_tiles))
        flat_ids = jnp.arange(n_local, dtype=jnp.int32)
        top_sq, top_ids, stats = ivf_scan_kernel_call(
            offs, qcodes, qf, qscales, r0,
            jnp.full((q, k), jnp.inf, jnp.float32),
            jnp.full((q, k), -1, jnp.int32),
            codes, corpus, flat_ids,
            bscales, eps, scale, k=k, block_q=block_q, block_c=block_c,
            block_d=block_d, cap_tiles=cap_tiles,
            interpret=interpret)
        top_ids = jnp.where(top_ids >= 0, base + top_ids, -1)
        top_sq, top_ids = merge_shards(top_sq, top_ids)
        dists = jnp.sqrt(jnp.maximum(top_sq, 0.0))
        if not with_stats:
            return dists, top_ids
        # Tile-level counters (cols 4-6) are broadcast to every query row
        # of a tile; stride-sample the first row per tile (lossless) before
        # summing, then reduce across shards.
        scan = jnp.concatenate([
            jnp.sum(stats[:, :4], axis=0),
            jnp.sum(stats[::block_q, 4:], axis=0),
        ])
        for ax in axes:
            scan = jax.lax.psum(scan, ax)
        return dists, top_ids, scan

    if quant == "int8":
        if with_stats and not fused:
            raise ValueError(
                "with_stats needs the fused megakernel route (fused=True): "
                "only the demand-paged kernel reports fetch counters")
        return shard_map(
            local_search_quant_fused if fused else local_search_quant,
            mesh=mesh,
            in_specs=(P(axes, None), P(axes, None), P(), P(), P(), P(), P()),
            out_specs=(P(), P(), P()) if with_stats else (P(), P()),
            check_vma=False,
        )
    if quant not in (None, "none"):
        raise ValueError(f"unknown quant mode: {quant!r}")
    if with_stats:
        raise ValueError("with_stats needs quant='int8' with fused=True")
    return shard_map(
        local_search,
        mesh=mesh,
        in_specs=(P(axes, None), P(), P(), P(), P()),
        out_specs=(P(), P()),
        check_vma=False,
    )


# ---------------------------------------------------------------------------
# Continuous-batching engines: mid-walk admission over the fused scans
# ---------------------------------------------------------------------------


def slo_signal(r_prev: float, r_new: float) -> float:
    """Observed DCO threshold-tightening rate over one wave, in [0, 1].

    0 means the wave-start r² did not move (a stalling walk); 1 means it
    collapsed — or became finite from an unseeded ``inf``, the strongest
    tightening a wave can report.  Pure host arithmetic on the wave-start
    thresholds the driver already computes; the kernel never sees it."""
    if not math.isfinite(r_prev):
        return 1.0 if math.isfinite(r_new) else 0.0
    if r_prev <= 0.0:
        return 0.0
    return float(min(max(1.0 - r_new / r_prev, 0.0), 1.0))


def slo_effort(signal: float, lo: float, hi: float) -> float:
    """Map a [0, 1] urgency signal onto an effort dial in [lo, hi].

    Monotone nondecreasing in ``signal`` and clamped to the [lo, hi] band —
    the two adaptation properties tests/test_continuous.py asserts.  With
    ``lo == hi`` the dial is a constant, which is how an SLO policy
    degenerates to the fixed-parameter engine bit-for-bit."""
    if hi < lo:
        raise ValueError(f"slo_effort needs hi >= lo, got lo={lo} hi={hi}")
    s = min(max(float(signal), 0.0), 1.0)
    return lo + (hi - lo) * s


@dataclasses.dataclass(frozen=True)
class SLOPolicy:
    """Per-query effort adaptation from the threshold-tightening rate.

    ``lo``/``hi`` bound the host-side effort dial — the frontier ``expand``
    of the graph walk, the probe allowance of the IVF scan.  A walk whose
    threshold stalls (low :func:`slo_signal`) is pushed toward ``hi`` so it
    converges inside its latency budget; a fast-tightening walk coasts at
    ``lo``.  ``stall_waves`` (optional) retires a query early after that
    many consecutive waves without any tightening — the ``serve.retire.
    stall`` path.  Adaptation touches ONLY host dials, never the kernel's
    screen threshold, so every returned distance is still exact; what it
    trades away is the batch oracle's bit-identity (a query may walk a
    narrower or wider frontier than the fixed engine).  ``slo=None`` (the
    ``--slo off`` default) bypasses the policy entirely and stays
    bit-identical to the fixed-parameter engine."""

    lo: float
    hi: float
    stall_waves: int | None = None

    def __post_init__(self):
        if self.hi < self.lo:
            raise ValueError(
                f"SLOPolicy needs hi >= lo, got lo={self.lo} hi={self.hi}")
        if self.stall_waves is not None and self.stall_waves < 1:
            raise ValueError(
                f"SLOPolicy stall_waves must be >= 1, got {self.stall_waves}")

    def dial(self, tightening: float) -> float:
        """Effort for one wave: monotone NONincreasing in the tightening
        signal (stalling → more effort), bounded to [lo, hi]."""
        return slo_effort(1.0 - tightening, self.lo, self.hi)


def parse_slo(spec) -> SLOPolicy | None:
    """Parse a ``--slo`` CLI spec: ``off``/``none``/empty → None,
    ``LO:HI`` or ``LO:HI:STALL_WAVES`` → :class:`SLOPolicy`."""
    if spec is None or isinstance(spec, SLOPolicy):
        return spec
    s = str(spec).strip().lower()
    if s in ("", "off", "none"):
        return None
    parts = s.split(":")
    if len(parts) not in (2, 3):
        raise ValueError(
            f"--slo spec {spec!r}: want LO:HI, LO:HI:STALL_WAVES, or 'off'")
    stall = int(parts[2]) if len(parts) == 3 else None
    return SLOPolicy(lo=float(parts[0]), hi=float(parts[1]),
                     stall_waves=stall)


@dataclasses.dataclass(frozen=True)
class RetiredQuery:
    """One query leaving the continuous engine: its results, its ledger,
    and why it retired (``frontier`` = converged, ``budget`` = wave budget
    exhausted, ``stall`` = SLO stall cutoff)."""

    handle: int
    dists: np.ndarray  # (K,)
    ids: np.ndarray  # (K,)
    stats: object  # GraphScanStats | FusedScanStats, qn=1 ledger
    waves: int
    reason: str
    degraded: bool


class ContinuousGraphEngine:
    """Mid-walk admission over the batched beam-scan megakernel.

    Every live query occupies its OWN ``block_q`` query tile — the query in
    row 0, pad rows exactly as the batch driver pads a one-query batch
    (``_prep_wave_state(index, q[None], ...)``) — and each wave stacks the
    live tiles into one launch, padded to a power-of-two tile count
    (``pow2_bucket``) so compiled shapes stay logarithmic in the live-set
    size.  The megakernel grid's query dimension is "parallel" and a tile
    reads only its own blocks (``-1`` frontier steps fully predicated), so
    the stacked launch is bit-identical, per tile, to launching each query
    alone: for ANY admission schedule, retirement order, and bucket
    compaction sequence, every query returns exactly the ids, distances,
    and byte ledgers of ``search_graph_fused(index, q[None], ...)`` serving
    it solo — the interleaving-invariance contract
    tests/test_continuous.py fuzzes.

    ``num_shards > 1`` runs the host-simulated sharded walk per wave:
    per-shard slab launches with the wave-start threshold FROZEN
    (``tighten=False``), windows merged via ``merge_shard_windows`` and
    bitmaps OR'd — the ``search_graph_sharded`` schedule, whose solo
    comparator is the ``num_shards=1, use_ref=True`` oracle.  Each wave
    consults the chaos harness for dead shards: queries admitted after a
    death start from the degraded state (fallback entry, tombstoned
    bitmap — bit-identical to the degraded solo oracle); queries mid-walk
    at the death get the dead ranges OR'd into their bitmaps and finish
    degraded (their history straddles the transition, so no solo oracle
    exists for them — they are flagged, not dropped).

    ``slo`` (an :class:`SLOPolicy` or ``--slo`` spec) adapts each query's
    frontier ``expand`` from its threshold-tightening rate and optionally
    retires stalled walks early; ``None`` keeps the engine bit-identical
    to the fixed-parameter batch oracle.
    """

    def __init__(self, index, *, k: int, ef: int = 48, expand: int = 2,
                 block_q: int | None = None, seed_r: bool = False,
                 decoupled: bool = True, route_mult: float = 1.0,
                 max_waves: int = 64, num_shards: int = 1, slo=None,
                 interpret: bool | None = None, use_ref: bool = False):
        from repro.index.graph import shard_graph_nodes
        from repro.kernels.ops import (
            auto_block_q, auto_interpret, graph_vis_words)

        if not index.has_fused:
            raise ValueError(
                "continuous graph serving needs build_graph(..., "
                "quant='int8')")
        if not 1 <= k <= ef:
            raise ValueError(f"need 1 <= k <= ef, got k={k} ef={ef}")
        if block_q is None:
            block_q = auto_block_q(
                auto_interpret() if interpret is None else interpret)
        self.index = index
        self.k = k
        self.ef = ef
        self.expand = expand
        self.block_q = block_q
        self.seed_r = seed_r
        self.decoupled = decoupled
        self.route_mult = route_mult
        self.max_waves = max_waves
        self.num_shards = num_shards
        self.slo = parse_slo(slo)
        self.interpret = interpret
        self.use_ref = use_ref
        self.thresh_col = (k - 1) if decoupled else (ef - 1)
        n = index.corpus_rot.shape[0]
        self._n = n
        self._dim = n and index.corpus_rot.shape[1]
        self._words = graph_vis_words(n)
        self._ranges = shard_graph_nodes(n, num_shards)
        a_block = index.adj_block
        if num_shards == 1:
            self._slabs = [(index.adj_rot, index.adj_codes, index.adj_ids)]
        else:
            self._slabs = [
                (index.adj_rot[b * a_block: (b + c) * a_block],
                 index.adj_codes[b * a_block: (b + c) * a_block],
                 index.adj_ids[b * a_block: (b + c) * a_block])
                for b, c in self._ranges
            ]
        self._slots: dict[int, dict] = {}
        self._next = 0
        self._tombs: tuple = ()
        self._wave_idx = 0

    # -- live-set management -------------------------------------------------

    def live_count(self) -> int:
        return len(self._slots)

    def _sync_chaos(self) -> None:
        """Refresh dead-shard tombstones from the chaos harness.  Newly
        dead ranges are OR'd into every LIVE walk's bitmap (mid-walk
        failover: the walk continues over the surviving corpus, flagged
        degraded); admissions after this point start from the degraded
        wave-0 state and stay bit-identical to the degraded solo oracle."""
        from repro.index.graph import dead_shard_tombstones
        from repro.kernels.ops import pack_vis_ranges
        from repro.runtime.chaos import current_chaos

        dead = current_chaos().dead_shards(self.num_shards)
        tombs = dead_shard_tombstones(self._n, self.num_shards, dead) \
            if dead else ()
        if tombs == self._tombs:
            return
        fresh = tuple(t for t in tombs if t not in self._tombs)
        self._tombs = tombs
        if fresh:
            bits = pack_vis_ranges(self._n, fresh)
            for slot in self._slots.values():
                slot["vis"] = slot["vis"] | bits[None, :]
                slot["degraded"] = True

    def admit(self, row: np.ndarray) -> int:
        """Admit one query mid-walk; returns its handle.  The slot state is
        freshly seeded from ``_prep_wave_state`` on the one-query batch —
        a backfilled slot can never inherit a retired walk's beam window
        (the stale-slot hazard tests/test_continuous.py regresses)."""
        from repro.index.graph import _prep_wave_state
        from repro.kernels.ops import pack_vis_ranges

        self._sync_chaos()
        row = np.asarray(row, np.float32)
        (_inv, q_sorted, _qt, _qp, _qn, entry, top_sq, top_ids,
         seed_vec) = _prep_wave_state(
            self.index, jnp.asarray(row[None]), k=self.k, ef=self.ef,
            block_q=self.block_q, seed_r=self.seed_r,
            tombstones=self._tombs)
        vis = np.zeros((1, self._words), np.int32)
        if self._tombs:
            vis |= pack_vis_ranges(self._n, self._tombs)[None, :]
        h = self._next
        self._next += 1
        self._slots[h] = dict(
            q=q_sorted, top_sq=top_sq, top_ids=top_ids, seed=seed_vec,
            vis=vis, entry=entry, depth=0,
            sem=np.zeros((4,), np.float64),
            s1=np.zeros((self.num_shards,), np.float64),
            s2=np.zeros((self.num_shards,), np.float64), exch=0.0,
            degraded=bool(self._tombs), r_prev=math.inf, stall=0,
            expand=self.expand)
        return h

    def shed(self, handle: int) -> None:
        """Drop a live walk without retiring it (deadline/error sheds)."""
        self._slots.pop(handle, None)

    def _finish(self, handle: int, reason: str) -> RetiredQuery:
        from repro.index.graph import _graph_sharded_stats, _graph_stats

        slot = self._slots.pop(handle)
        top_sq_f = slot["top_sq"][:1]  # the qn=1 crop of the batch epilogue
        top_ids_f = slot["top_ids"][:1]
        dists = np.sqrt(np.maximum(top_sq_f, 0.0))[0, : self.k]
        ids = top_ids_f[0, : self.k].astype(np.int32)
        if self.num_shards == 1:
            stats = _graph_stats(
                self.index, dim=self._dim, k=self.k, seed_r=self.seed_r,
                qn=1, waves=slot["depth"], sem=slot["sem"],
                s1_tiles=float(slot["s1"].sum()),
                s2_slabs=float(slot["s2"].sum()))
        else:
            stats = _graph_sharded_stats(
                self.index, dim=self._dim, k=self.k, seed_r=self.seed_r,
                qn=1, waves=slot["depth"], sem=slot["sem"],
                s1_tiles=slot["s1"], s2_slabs=slot["s2"],
                exch_bytes=slot["exch"], num_shards=self.num_shards,
                tombstones=self._tombs)
        return RetiredQuery(handle=handle, dists=dists, ids=ids, stats=stats,
                            waves=slot["depth"], reason=reason,
                            degraded=slot["degraded"])

    # -- the wave step -------------------------------------------------------

    def step(self) -> list[RetiredQuery]:
        """Run ONE frontier wave over the whole live set; returns the
        queries that retired (converged frontier, wave budget, or SLO
        stall).  Safe to call with an empty live set (returns [])."""
        from repro.index.graph import merge_shard_windows, _select_wave
        from repro.kernels.ops import (
            graph_scan_kernel, pad_live_rows, pow2_bucket, unpack_vis,
        )
        from repro.quant.accounting import frontier_exchange_bytes
        from repro.runtime.chaos import current_chaos

        self._sync_chaos()
        chaos = current_chaos()
        chaos.on_wave(self._wave_idx)
        self._wave_idx += 1
        retired: list[RetiredQuery] = []
        live: list[int] = []
        picks: dict[int, tuple[list, np.ndarray]] = {}
        for h in list(self._slots):
            slot = self._slots[h]
            if slot["depth"] >= self.max_waves:
                retired.append(self._finish(h, "budget"))
                continue
            r0 = np.minimum(slot["seed"], slot["top_sq"][:, self.thresh_col])
            if slot["depth"] == 0:
                sel = [slot["entry"]]
            else:
                sel = _select_wave(
                    slot["top_sq"], slot["top_ids"],
                    unpack_vis(slot["vis"], self._n),
                    r0 * self.route_mult, q_tiles=1, block_q=self.block_q,
                    qn=1, expand=slot["expand"], ef=self.ef)[0]
                if not sel:
                    retired.append(self._finish(h, "frontier"))
                    continue
            picks[h] = (sel, r0)
            live.append(h)
        if not live:
            return retired

        bq = self.block_q
        n_live = len(live)
        bucket = pow2_bucket(n_live)
        steps = pow2_bucket(max(len(picks[h][0]) for h in live))
        offs = np.full((n_live, steps), -1, np.int32)
        for t, h in enumerate(live):
            offs[t, : len(picks[h][0])] = picks[h][0]
        # Stack the live tiles and pad to the pow2 bucket with the exact
        # inert values the batch driver pads one-query batches with.
        q_cat = pad_live_rows(
            np.concatenate([self._slots[h]["q"] for h in live]),
            n_live * bq, bucket * bq, fill=0.0)
        top_sq = pad_live_rows(
            np.concatenate([self._slots[h]["top_sq"] for h in live]),
            n_live * bq, bucket * bq, fill=np.inf)
        top_ids = pad_live_rows(
            np.concatenate([self._slots[h]["top_ids"] for h in live]),
            n_live * bq, bucket * bq, fill=-1)
        r0_cat = pad_live_rows(
            np.concatenate([picks[h][1] for h in live]),
            n_live * bq, bucket * bq, fill=0.0)
        vis_cat = pad_live_rows(
            np.concatenate([self._slots[h]["vis"] for h in live]),
            n_live, bucket, fill=0)
        offs = pad_live_rows(offs, n_live, bucket, fill=-1)

        with current_tracer().span("continuous.wave", live=n_live,
                                   bucket=bucket, steps=steps):
            if self.num_shards == 1:
                sq, ids_, st, vis_out = graph_scan_kernel(
                    self.index.estimator, jnp.asarray(q_cat),
                    jnp.asarray(offs), jnp.asarray(top_sq),
                    jnp.asarray(top_ids), jnp.asarray(r0_cat),
                    *self._slabs[0], self.index.gscales,
                    jnp.asarray(vis_cat), vis_base=0, vis_nodes=self._n,
                    ef=self.ef, thresh_col=self.thresh_col, block_q=bq,
                    block_c=self.index.adj_block,
                    block_d=self.index.scan_block_d, tighten=True,
                    interpret=self.interpret, use_ref=self.use_ref)
                t_sq = np.asarray(sq, np.float32)
                t_ids = np.asarray(ids_, np.int32)
                t_vis = np.asarray(vis_out, np.int32)
                st_sh = np.asarray(st)[None]
            else:
                g_sq, g_ids, g_vis, g_st = [], [], [], []
                for s, (b, c) in enumerate(self._ranges):
                    own = (offs >= b) & (offs < b + c)
                    offs_s = np.where(own, offs - b, -1).astype(np.int32)
                    sq_s, id_s, st_s, vis_s = graph_scan_kernel(
                        self.index.estimator, jnp.asarray(q_cat),
                        jnp.asarray(offs_s), jnp.asarray(top_sq),
                        jnp.asarray(top_ids), jnp.asarray(r0_cat),
                        *self._slabs[s], self.index.gscales,
                        jnp.asarray(vis_cat), vis_base=b, vis_nodes=self._n,
                        ef=self.ef, thresh_col=self.thresh_col, block_q=bq,
                        block_c=self.index.adj_block,
                        block_d=self.index.scan_block_d, tighten=False,
                        interpret=self.interpret, use_ref=self.use_ref)
                    g_sq.append(jnp.asarray(sq_s))
                    g_ids.append(jnp.asarray(id_s))
                    g_vis.append(np.asarray(vis_s, np.int32))
                    g_st.append(np.asarray(st_s))
                m_sq, m_ids = merge_shard_windows(
                    jnp.stack(g_sq), jnp.stack(g_ids), ef=self.ef)
                t_sq = np.asarray(m_sq, np.float32)
                t_ids = np.asarray(m_ids, np.int32)
                t_vis = g_vis[0]
                for v in g_vis[1:]:
                    t_vis = t_vis | v
                st_sh = np.stack(g_st)

        stalled: list[int] = []
        for t, h in enumerate(live):
            slot = self._slots[h]
            slot["top_sq"] = t_sq[t * bq: (t + 1) * bq]
            slot["top_ids"] = t_ids[t * bq: (t + 1) * bq]
            slot["vis"] = t_vis[t: t + 1]
            for s in range(self.num_shards):
                # Row 0 of the slot's tile is its only real query — the
                # same qn=1 crop the solo oracle's epilogue sums over.
                slot["sem"] += st_sh[s][t * bq, :4]
                slot["s1"][s] += float(st_sh[s][t * bq, 5])
                slot["s2"][s] += float(st_sh[s][t * bq, 4])
            if self.num_shards > 1:
                # The exchange ledger a SOLO run of this query would book
                # this wave: its own frontier width sets the step count,
                # not the stacked launch's max (the stacked step table is
                # an execution artifact; -1 steps ship nothing).
                slot["exch"] += frontier_exchange_bytes(
                    num_shards=self.num_shards, queries=bq, ef=self.ef,
                    vis_words=self._words, q_tiles=1,
                    steps=pow2_bucket(len(picks[h][0])))
            slot["depth"] += 1
            r_new = float(np.minimum(slot["seed"],
                                     slot["top_sq"][:, self.thresh_col])[0])
            if self.slo is not None:
                rho = slo_signal(slot["r_prev"], r_new)
                slot["expand"] = max(1, int(round(self.slo.dial(rho))))
                slot["stall"] = 0 if rho > 0.0 else slot["stall"] + 1
                if (self.slo.stall_waves is not None
                        and slot["stall"] >= self.slo.stall_waves):
                    stalled.append(h)
            slot["r_prev"] = r_new
        for h in stalled:
            retired.append(self._finish(h, "stall"))
        return retired


class ContinuousIVFEngine:
    """Mid-walk admission over the fused IVF wave scan.

    Each live query owns one ``block_q`` tile (query in row 0, pad rows
    zero — the wrapper's own padding for a one-query batch) and a probe
    plan computed at admission by the SAME tile router the batch path uses
    (``index.ivf._route_tiles`` on the one-query batch).  Every engine
    wave advances each live slot by ``probe_chunk`` probes of its plan in
    one stacked launch: the slot's top-K window and threshold re-enter the
    kernel through the seed inputs, and the in-kernel carry rule
    ``r² ← min(r², top_sq[k-1])`` makes the chunked sequence bit-identical
    to the batch oracle's single launch (exact resume; needs the aligned
    CSR layout — ``128 % block_c == 0`` — which the builder guarantees).
    A slot retires when its probe allowance is consumed.  Stats columns
    are integer-valued f32, so summing chunk totals host-side reproduces
    the single-launch counters exactly and the per-query
    ``FusedScanStats`` ledger compares ``==`` against
    ``search_ivf_fused(index, q[None], ...)``.

    ``slo`` adapts the per-query probe allowance within [lo, hi] from the
    tightening rate (and can retire stalled scans early); ``None`` keeps
    the engine bit-identical to the fixed-``n_probe`` oracle.
    """

    def __init__(self, index, *, k: int, n_probe: int = 8,
                 block_q: int | None = None, block_c: int = 128,
                 probe_chunk: int = 2, seed_r: bool = True, slo=None,
                 interpret: bool | None = None, use_ref: bool = False):
        from repro.kernels.ops import auto_block_q, auto_interpret

        if not index.has_fused:
            raise ValueError(
                "continuous IVF serving needs build_ivf(..., quant='int8')")
        if 128 % block_c:
            raise ValueError(
                f"continuous IVF serving needs 128 % block_c == 0 (aligned "
                f"CSR windows are what make the chunked probe carry exact), "
                f"got block_c={block_c}")
        if probe_chunk < 1:
            raise ValueError(f"probe_chunk must be >= 1, got {probe_chunk}")
        if block_q is None:
            block_q = auto_block_q(
                auto_interpret() if interpret is None else interpret)
        self.index = index
        self.k = k
        self.n_probe = min(n_probe, index.n_clusters)
        self.block_q = block_q
        self.block_c = block_c
        self.probe_chunk = probe_chunk
        self.seed_r = seed_r
        self.slo = parse_slo(slo)
        self.interpret = interpret
        self.use_ref = use_ref
        self._slots: dict[int, dict] = {}
        self._next = 0
        self._wave_idx = 0

    def live_count(self) -> int:
        return len(self._slots)

    def admit(self, row: np.ndarray) -> int:
        from repro.index.ivf import _quant_seed_rsq, _route_tiles

        row = np.asarray(row, np.float32)
        q_rot = self.index.estimator.rotate(jnp.asarray(row[None]))
        (_o, _i, q_sorted, tile_buckets, window_starts,
         window_rows) = _route_tiles(self.index, q_rot,
                                     n_probe=self.n_probe,
                                     block_q=self.block_q)
        if self.seed_r:
            r0 = float(_quant_seed_rsq(
                self.index, q_sorted, tile_buckets[:, 0], self.k)[0])
        else:
            r0 = math.inf
        h = self._next
        self._next += 1
        self._slots[h] = dict(
            q=np.asarray(q_sorted, np.float32),
            starts=np.asarray(window_starts, np.int32)[0],
            rows=np.asarray(window_rows, np.int32)[0],
            pos=0, r=r0,
            top_sq=np.full((1, self.k), np.inf, np.float32),
            top_ids=np.full((1, self.k), -1, np.int32),
            sem=np.zeros((4,), np.float64), s1=0.0, s2=0.0,
            n_eff=self.n_probe, launches=0, r_prev=math.inf, stall=0)
        return h

    def shed(self, handle: int) -> None:
        self._slots.pop(handle, None)

    def _finish(self, handle: int, reason: str) -> RetiredQuery:
        from repro.index.ivf import _fused_stats

        slot = self._slots.pop(handle)
        dists = np.sqrt(np.maximum(slot["top_sq"][0], 0.0))
        ids = slot["top_ids"][0].astype(np.int32)
        # One synthesized qn=1 stats row re-enters the shared epilogue:
        # cols 0-3 are the chunk-summed counters, cols 4-5 the fetch
        # totals (block_q=1 makes the tile stride-sample the row itself).
        st_row = np.asarray(
            [[*slot["sem"], slot["s2"], slot["s1"]]], np.float32)
        stats = _fused_stats(self.index, st_row, qn=1, k=self.k, block_q=1,
                             block_c=self.block_c, seed_r=self.seed_r)
        return RetiredQuery(handle=handle, dists=dists, ids=ids, stats=stats,
                            waves=slot["launches"], reason=reason,
                            degraded=False)

    def step(self) -> list[RetiredQuery]:
        """Advance every live slot by one probe chunk in one stacked
        launch; returns the slots whose probe allowance is consumed."""
        from repro.kernels.ops import (
            ivf_scan_kernel, pad_live_rows, pow2_bucket,
        )
        from repro.runtime.chaos import current_chaos

        current_chaos().on_wave(self._wave_idx)
        self._wave_idx += 1
        retired: list[RetiredQuery] = []
        live: list[int] = []
        for h in list(self._slots):
            slot = self._slots[h]
            if slot["pos"] >= slot["n_eff"]:
                retired.append(self._finish(h, "frontier"))
                continue
            live.append(h)
        if not live:
            return retired

        bq = self.block_q
        chunk = self.probe_chunk
        n_live = len(live)
        bucket = pow2_bucket(n_live)
        dim = self._slots[live[0]]["q"].shape[1]

        def tile(slot):
            q = np.zeros((bq, dim), np.float32)
            q[0] = slot["q"][0]
            return q

        def window(slot, arr):
            out = np.zeros((chunk,), np.int32)
            span = arr[slot["pos"]: slot["pos"] + chunk]
            out[: len(span)] = span
            # Past-the-plan probes carry (start=0, rows=0): zero-row
            # aligned windows span zero tiles, so the kernel ships nothing.
            if arr is slot["rows"]:
                out[len(span):] = 0
            return out

        q_cat = pad_live_rows(
            np.concatenate([tile(self._slots[h]) for h in live]),
            n_live * bq, bucket * bq, fill=0.0)
        r0_cat = np.zeros((n_live * bq,), np.float32)
        t0_sq = np.full((n_live * bq, self.k), np.inf, np.float32)
        t0_ids = np.full((n_live * bq, self.k), -1, np.int32)
        starts = np.zeros((n_live, chunk), np.int32)
        rows = np.zeros((n_live, chunk), np.int32)
        for t, h in enumerate(live):
            slot = self._slots[h]
            r0_cat[t * bq] = min(slot["r"], np.float32(np.inf)) \
                if math.isfinite(slot["r"]) else np.inf
            t0_sq[t * bq] = slot["top_sq"][0]
            t0_ids[t * bq] = slot["top_ids"][0]
            span = slot["starts"][slot["pos"]: slot["pos"] + chunk]
            starts[t, : len(span)] = span
            rows[t, : len(span)] = \
                slot["rows"][slot["pos"]: slot["pos"] + chunk]
        r0_cat = pad_live_rows(r0_cat, n_live * bq, bucket * bq, fill=0.0)
        t0_sq = pad_live_rows(t0_sq, n_live * bq, bucket * bq, fill=np.inf)
        t0_ids = pad_live_rows(t0_ids, n_live * bq, bucket * bq, fill=-1)
        starts = pad_live_rows(starts, n_live, bucket, fill=0)
        rows = pad_live_rows(rows, n_live, bucket, fill=0)

        with current_tracer().span("continuous.wave", live=n_live,
                                   bucket=bucket, chunk=chunk):
            top_sq, top_ids, st = ivf_scan_kernel(
                self.index.estimator, jnp.asarray(q_cat),
                jnp.asarray(starts), jnp.asarray(rows), self.index.flat_rot,
                self.index.flat_codes, self.index.flat_ids,
                self.index.bscales, jnp.asarray(r0_cat),
                jnp.asarray(t0_sq), jnp.asarray(t0_ids), k=self.k,
                max_bucket=self.index.max_bucket, block_q=bq,
                block_c=self.block_c, block_d=self.index.scan_block_d,
                starts_aligned=True, interpret=self.interpret,
                use_ref=self.use_ref)
        top_sq = np.asarray(top_sq, np.float32)
        top_ids = np.asarray(top_ids, np.int32)
        st = np.asarray(st)

        stalled: list[int] = []
        for t, h in enumerate(live):
            slot = self._slots[h]
            slot["top_sq"] = top_sq[t * bq: t * bq + 1]
            slot["top_ids"] = top_ids[t * bq: t * bq + 1]
            slot["sem"] += st[t * bq, :4]
            slot["s1"] += float(st[t * bq, 5])
            slot["s2"] += float(st[t * bq, 4])
            # The in-kernel carry rule, replayed host-side: the next
            # chunk's r0 is exactly where the single launch would be.
            slot["r"] = min(slot["r"], float(slot["top_sq"][0, self.k - 1]))
            slot["pos"] += chunk
            slot["launches"] += 1
            if self.slo is not None:
                rho = slo_signal(slot["r_prev"], slot["r"])
                slot["n_eff"] = max(1, min(self.n_probe,
                                           int(round(self.slo.dial(rho)))))
                slot["stall"] = 0 if rho > 0.0 else slot["stall"] + 1
                if (self.slo.stall_waves is not None
                        and slot["stall"] >= self.slo.stall_waves):
                    stalled.append(h)
            slot["r_prev"] = slot["r"]
        for h in stalled:
            retired.append(self._finish(h, "stall"))
        return retired
