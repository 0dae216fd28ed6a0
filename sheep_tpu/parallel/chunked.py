"""Mesh-sharded chunked build: the bounded-execution driver composed with
the device mesh.

Why this module exists (round-3 hardware evidence, PERF_NOTES.md): on real
TPU hardware a data-dependent ``lax.while_loop`` faults once its wall time
outgrows the backend's per-execution budget, so the production single-chip
path is the host-orchestrated chunked driver (ops/forest.py
``reduce_links_hosted``: J rounds per dispatch via ``fori_loop``, host sync
+ compaction between dispatches).  The first-generation mesh path
(parallel/build.py) still ran the while_loop *inside* ``shard_map`` — the
exact shape that faulted.  This module is the mesh analog of the chunked
driver: every device dispatch is a bounded ``fori_loop`` under ``shard_map``,
and the host loop reads one replicated stats vector per chunk.

Two round flavors compose the reference's map/reduce split
(SURVEY §2.6, lib/jnode.cpp:203-250):

  local rounds  (map)   — each worker reduces its own edge shard's links
                          with zero per-round communication: sort + star->
                          chain rewrite + jump against the LOCAL min-up
                          table.  Converged shards hold per-worker partial
                          forests over the shared sequence — exactly the
                          reference's per-rank JTree build.
  global rounds (reduce)— same transform but the jump table is the GLOBAL
                          min-up-neighbor: per-shard scatter-min tables
                          combined with ``lax.pmin`` over the axis (one
                          [n+1] all-reduce per round, the mpi_merge
                          analog).  Soundness: the threshold-connectivity
                          argument of ops/forest.py only needs each f-edge
                          to exist SOMEWHERE in the global multiset, so
                          jumping any shard's lo through the global f
                          preserves global threshold connectivity; local
                          sort/rewrite is a per-subset transform and was
                          already sound.  At global fixpoint every live
                          link (lo, hi) has f(lo) == hi, i.e. the union of
                          shards is one functional forest — the answer.

Termination is unchanged: every applied rewrite strictly increases some lo
bounded by n, so both phases converge; chunking only bounds how much runs
per dispatch.  Compaction slices the LOCAL axis of the [W, B] link arrays
(per-row sort guarantees each row's live prefix), so shards shrink in
lockstep to the pmax of per-row live counts.

Round 5 adds the **gather-tail** (reduce_links_sharded docstring): global
rounds pay one [n+1] pmin each, but the measured dense trajectory does
its mass-kill in ~3 rounds and then spends 20+ rounds collapsing chains
on a plateaued live window — so once the whole window is cheaper to move
than a few more table reduces, the links all_gather ONCE and the tail
runs replicated through the single-chip chunk loop (depth tiers +
vremap_compact vertex windowing), with zero further collectives.  That
cuts per-build collective payload ~4-7x at W=8 (MESHBENCH_r05) and is
the mesh analog of both the reference's single MPI_Reduce
(lib/jnode.cpp:228-241) and the hybrid's handoff philosophy.
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from ..obs import trace as _obs
from ..ops.forest import (_CHUNK_SCHEDULE as _SCHEDULE, _depth_tier,
                          _lift_descend, _rewrite_sorted, pst_weights,
                          sort_links)
from ..ops.sort import degree_order
from .mesh import AXIS, make_mesh

_JROUNDS = 8
_LEVELS = 10
_FIRST_LEVELS = 4


def _row_round(lo, hi, n: int, levels: int, f_combine):
    """One chunk round on a worker's local [B] link row.

    ``f_combine``: identity for local (map) rounds, ``lax.pmin`` over the
    workers axis for global (reduce) rounds.  Returns (lo, hi, moved, live).
    """
    sent = jnp.int32(n)
    lo, hi = sort_links(lo, hi)
    live = jnp.sum(lo != sent, dtype=jnp.int32)
    lo, hi, rewrites = _rewrite_sorted(lo, hi, n)
    # one-step min-up table, combined across the mesh BEFORE lifting so
    # every worker lifts the same (global, for reduce rounds) f
    f = jnp.full(n + 1, sent, jnp.int32).at[lo].min(hi)
    f = f_combine(f)
    lo, jumped = _lift_descend(lo, hi, n, levels, f)
    return lo, hi, rewrites + jumped, live


@functools.partial(jax.jit,
                   static_argnames=("n", "mesh", "levels", "jrounds",
                                    "global_f"))
def chunk_sharded(lo, hi, n: int, mesh, levels: int, jrounds: int,
                  global_f: bool):
    """``jrounds`` bounded rounds on [W, B] sharded links in ONE dispatch.

    Returns (lo, hi, stats) with stats int32 [2] = (moved_total,
    live_max_per_row) replicated — one host fetch per chunk, matching the
    single-sync contract of ops.forest.fixpoint_chunk.
    """
    def body(lo, hi):
        lo = lo[0]  # [1, B] local block -> [B]
        hi = hi[0]
        combine = (lambda f: lax.pmin(f, AXIS)) if global_f \
            else (lambda f: f)

        def one(_, st):
            lo, hi, _, _ = st
            return _row_round(lo, hi, n, levels, combine)

        st = (lo.astype(jnp.int32), hi.astype(jnp.int32),
              jnp.int32(0), jnp.int32(lo.shape[0]))
        lo, hi, moved, live = lax.fori_loop(0, jrounds, one, st)
        stats = jnp.stack([lax.psum(moved, AXIS), lax.pmax(live, AXIS)])
        return lo[None, :], hi[None, :], stats

    fn = shard_map(body, mesh=mesh,
                   in_specs=(P(AXIS, None), P(AXIS, None)),
                   out_specs=(P(AXIS, None), P(AXIS, None), P()),
                   check_vma=False)
    return fn(lo, hi)


@functools.partial(jax.jit, static_argnames=("n", "mesh"))
def parent_sharded_local(lo, hi, n: int, mesh):
    """Per-shard parent extraction from per-shard converged links: [W, n]
    stacked, NO cross-worker combine — each row is that worker's partial
    forest over the shared sequence (the `-i`-without-`-r` map phase)."""
    def body(lo, hi):
        sent = jnp.int32(n)
        p = jnp.full(n + 1, sent, jnp.int32).at[
            lo[0].astype(jnp.int32)].min(hi[0].astype(jnp.int32))[:n]
        return p[None, :]

    fn = shard_map(body, mesh=mesh,
                   in_specs=(P(AXIS, None), P(AXIS, None)),
                   out_specs=P(AXIS, None), check_vma=False)
    return fn(lo, hi)


@functools.partial(jax.jit, static_argnames=("n", "mesh"))
def parent_sharded(lo, hi, n: int, mesh):
    """Global parent extraction from converged sharded links: per-shard
    scatter-min pmin-combined (valid once the union forms a forest)."""
    def body(lo, hi):
        sent = jnp.int32(n)
        p = jnp.full(n + 1, sent, jnp.int32).at[
            lo[0].astype(jnp.int32)].min(hi[0].astype(jnp.int32))
        return lax.pmin(p, AXIS)[:n]

    fn = shard_map(body, mesh=mesh,
                   in_specs=(P(AXIS, None), P(AXIS, None)),
                   out_specs=P(), check_vma=False)
    return fn(lo, hi)


@functools.partial(jax.jit, static_argnames=("n", "mesh", "with_pos",
                                              "local_pst"))
def prep_sharded(tail, head, n: int, mesh, pos=None, with_pos: bool = False,
                 local_pst: bool = False):
    """Degree sort + link mapping over the mesh (the `-i` phase).

    tail/head int32 [W, B] sharded (pad with n).  Returns (seq, pos, m,
    lo [W, B], hi [W, B], pst) with everything but lo/hi replicated.
    Matches parallel.build._sharded_build's sequence/pst semantics.
    ``local_pst``: keep pst per-worker ([W, n] stacked, each row counting
    only that shard's edges) for the map-only partials path instead of
    the psum-combined total.
    """
    def body(t, h, posr):
        sent = jnp.int32(n)
        t = t[0].astype(jnp.int32)
        h = h[0].astype(jnp.int32)
        if posr is None:
            deg_local = jnp.zeros(n + 1, jnp.int32).at[t].add(1).at[h].add(1)
            deg = lax.psum(deg_local, AXIS)[:n]
            seq, pos_r, m = degree_order(deg)
        else:
            posi = posr.astype(jnp.int32)
            absent = (posi < 0) | (posi >= n)
            pos_r = jnp.where(absent, sent, posi)
            seq = jnp.full(n, sent, jnp.int32)
            vids = jnp.arange(n, dtype=jnp.int32)
            seq = seq.at[jnp.where(absent, n, pos_r)].set(vids, mode="drop")
            m = jnp.int32(n) - jnp.sum(absent, dtype=jnp.int32)
        pos_ext = jnp.concatenate([pos_r, jnp.full((1,), sent, jnp.int32)])
        pt = pos_ext[jnp.minimum(t, jnp.int32(n))]
        ph = pos_ext[jnp.minimum(h, jnp.int32(n))]
        lo = jnp.minimum(pt, ph)
        hi = jnp.maximum(pt, ph)
        # pst counts every edge at its present earlier endpoint, including
        # edges to absent vids (jtree.cpp:47-49); self/pad (lo==hi) never
        pst_local = pst_weights(jnp.where(lo == hi, sent, lo), n)
        dead = (lo >= hi) | (hi >= sent)
        lo = jnp.where(dead, sent, lo)
        hi = jnp.where(dead, sent, hi)
        if local_pst:
            return (seq, pos_r, m, lo[None, :], hi[None, :],
                    pst_local[None, :])
        return (seq, pos_r, m, lo[None, :], hi[None, :],
                lax.psum(pst_local, AXIS))

    pst_spec = P(AXIS, None) if local_pst else P()
    if with_pos:
        fn = shard_map(lambda t, h, p: body(t, h, p), mesh=mesh,
                       in_specs=(P(AXIS, None), P(AXIS, None), P()),
                       out_specs=(P(), P(), P(), P(AXIS, None),
                                  P(AXIS, None), pst_spec),
                       check_vma=False)
        return fn(tail, head, pos)
    fn = shard_map(lambda t, h: body(t, h, None), mesh=mesh,
                   in_specs=(P(AXIS, None), P(AXIS, None)),
                   out_specs=(P(), P(), P(), P(AXIS, None),
                              P(AXIS, None), pst_spec),
                   check_vma=False)
    return fn(tail, head)


def _pad_pow2_cols(x: int, lo_cap: int = 1 << 10) -> int:
    p = lo_cap
    while p < x:
        p <<= 1
    return p


@functools.partial(jax.jit, static_argnames=("mesh",))
def gather_links_replicated(lo, hi, mesh):
    """One all_gather of the live link window: [W, B] sharded -> flat
    [W*B] replicated.  The single collective that hands the reduce TAIL
    off the mesh (see reduce_links_sharded's gather-tail)."""
    def body(lo, hi):
        l = lax.all_gather(lo[0], AXIS)
        h = lax.all_gather(hi[0], AXIS)
        return l.reshape(-1), h.reshape(-1)

    fn = shard_map(body, mesh=mesh,
                   in_specs=(P(AXIS, None), P(AXIS, None)),
                   out_specs=(P(), P()), check_vma=False)
    return fn(lo, hi)


def _gather_tail_enabled(override: bool | None) -> bool:
    import os
    if override is not None:
        return override
    return os.environ.get("SHEEP_MESH_GATHER_TAIL", "1") != "0"


def _tail_shard_enabled(override: bool | None) -> bool:
    """Round-6 sharded tail gate (SHEEP_MESH_TAIL_SHARD, default on):
    see reduce_links_sharded — the round-5 gather-tail made the plateau
    collective-free but REPLICATED, so W-1 chips re-derived the same
    chain collapse; the sharded tail splits that work by vertex window
    so per-chip tail work falls with W."""
    import os
    if override is not None:
        return override
    return os.environ.get("SHEEP_MESH_TAIL_SHARD", "1") != "0"


def hi_window_bounds(sorted_hi, cnt, w: int, sent):
    """Equal-count hi-QUANTILE window boundaries: ``[w + 1]`` int32 value
    bounds over one hi-sorted array (``cnt`` live entries; sentinels
    ``== sent`` sort last), so window k keeps the links whose hi falls in
    ``[bounds[k], bounds[k+1])`` — ~cnt/w links each up to hub ties.

    THE windowing rule, shared by the mesh sharded tail
    (:func:`shard_links_by_window`) and the hybrid's streaming windowed
    handoff (ops.build), so the two partitions cannot drift.  Value
    quantiles, not equal-width spans: equal width was measured badly
    skewed on power-law inputs (70% of live links in one window at W=8).
    """
    dt = sorted_hi.dtype
    if w > 1:
        ks = (jnp.arange(1, w, dtype=jnp.int32) * cnt) // jnp.int32(w)
        mid = sorted_hi[ks]
    else:
        mid = jnp.zeros((0,), dt)
    return jnp.concatenate([jnp.zeros((1,), dt), mid,
                            jnp.full((1,), sent, dt)])


@functools.partial(jax.jit, static_argnames=("n", "mesh"))
def shard_links_by_window(lo, hi, n: int, mesh):
    """Replicated flat links -> [W, B] sharded by CONTIGUOUS hi window.

    Window boundaries are the live-count QUANTILES of the hi
    distribution (one replicated sort; deterministic, so every worker
    derives identical boundaries with zero communication): worker i
    keeps the links whose hi falls in [q_i, q_{i+1}) with q_0 = 0 and
    q_W = n, i.e. ~live/W links each.  Equal-width windows were
    measured badly skewed on power-law graphs (70% of the live links on
    one chip at W=8 — the plateau window concentrates in the middle of
    the position space); value-quantiles balance up to hub ties, and a
    single heavy hi is a STAR, which one local sort-rewrite collapses
    anyway.  Soundness is the map-phase argument: local rounds are
    per-subset transforms, and ANY partition of the multiset preserves
    union threshold connectivity.  Windows are contiguous ON PURPOSE:
    chains ascend through positions, so a contiguous window keeps each
    chain segment whole on one worker where local rounds can collapse
    it; a modulo shard would scatter every chain and leave the local
    phase nothing to do.
    """
    w = mesh.size

    def body(lo, hi):
        i = lax.axis_index(AXIS).astype(jnp.int32)
        sent = jnp.int32(n)
        live = lo < sent
        cnt = jnp.sum(live, dtype=jnp.int32)
        sh = lax.sort(hi)  # sentinels (= n) sort last
        bounds = hi_window_bounds(sh, cnt, w, sent)
        lower = bounds[i]
        upper = bounds[i + 1]
        mine = live & (hi >= lower) & (hi < upper)
        return (jnp.where(mine, lo, sent)[None, :],
                jnp.where(mine, hi, sent)[None, :])

    fn = shard_map(body, mesh=mesh, in_specs=(P(), P()),
                   out_specs=(P(AXIS, None), P(AXIS, None)),
                   check_vma=False)
    return fn(lo, hi)


@functools.partial(jax.jit, static_argnames=("n", "mesh"))
def row_live_counts(lo, n: int, mesh):
    """Replicated [W] vector of per-row live-link counts (the sharded
    tail's per-chip work observability; measurement path only)."""
    def body(lo):
        c = jnp.sum(lo[0] != jnp.int32(n), dtype=jnp.int32)
        return lax.all_gather(c, AXIS)

    fn = shard_map(body, mesh=mesh, in_specs=(P(AXIS, None),),
                   out_specs=P(), check_vma=False)
    return fn(lo)


def _tail_shard_local_rounds() -> int:
    """Round cap for the sharded tail's local pass
    (SHEEP_MESH_TAIL_SHARD_ROUNDS, default 5 — the chunk schedule's
    probing prefix, where the mass dedupe/star-collapse lands): past it
    the marginal local round retires little (the window's own straggler
    crawl), while the replicated finish pays ~finish_live * round for
    EVERY extra round it has to grind — the 2^18 model measured cap 13
    costing W=2 more per-chip work than no shard at all, and cap 5
    strictly decreasing across W=2/4/8."""
    import os
    return int(os.environ.get("SHEEP_MESH_TAIL_SHARD_ROUNDS", "5"))


def _gather_tail_factor() -> float:
    """Gather when W * cols <= factor * (n+1).  Default 2.0: the gather
    moves 8 * W * cols bytes, i.e. <= 4 pmin-round payloads at the
    threshold — and the measured dense trajectory (2^13-2^18 traces)
    pays ~3 sharded rounds to mass-kill and then 20+ plateau rounds
    that the gather-tail makes collective-free.  Row padding makes the
    plateau window ~2(n+1), so factor 1.0 would never fire densely."""
    import os
    return float(os.environ.get("SHEEP_MESH_GATHER_FACTOR", "2.0"))


def reduce_links_sharded(lo, hi, n: int, mesh, global_f: bool,
                         levels: int = _LEVELS, jrounds: int = _JROUNDS,
                         first_levels: int = _FIRST_LEVELS,
                         fetch=None, gather_tail: bool | None = None,
                         tail_shard: bool | None = None,
                         comm: dict | None = None, runtime=None,
                         max_rounds: int | None = None):
    """Host-orchestrated chunk loop on [W, B] sharded links.

    ``global_f`` False = map phase (per-shard independent), True = reduce
    phase (per-round pmin of the jump table).  Returns (lo, hi, rounds,
    replicated) — replicated False: [W, B] sharded with per-row live
    prefixes; True: flat replicated arrays (the gather-tail fired).
    ``fetch``: replicated-array -> numpy (multi-process safe override;
    default np.asarray).

    **Gather-tail (round-5, VERDICT r04 item 4 — the ICI-honest reduce).**
    A global round costs one [n+1] int32 all-reduce (4(n+1) bytes of
    pmin payload per worker per round) no matter how few links remain,
    and most global rounds run AFTER the early mass-kill has collapsed
    the live set — the round-4 design paid ~30 full-table collectives
    per build where the reference pays one MPI_Reduce total
    (lib/jnode.cpp:228-241).  So once the whole live window is cheaper
    to move than ~SHEEP_MESH_GATHER_FACTOR more pmin rounds
    (W * cols <= factor * (n+1), i.e. one 8*W*cols-byte all_gather vs
    8(n+1) bytes for a round-trip-equivalent of table reduces), the
    links all_gather ONCE into replicated arrays and the tail runs
    through the single-chip chunk loop (ops.forest.reduce_links_hosted)
    with ZERO further collectives — executed SPMD-replicated, so every
    worker deterministically holds the identical result, and the tail
    inherits the single-chip kit: depth-tier escalation, vremap_compact,
    and the round-6 plateau scheduler + straggler assist.
    Soundness: the gathered multiset is exactly the union of shard link
    sets — the same global threshold connectivity — and the forest is a
    function of threshold connectivity only.  SHEEP_MESH_GATHER_TAIL=0
    (or gather_tail=False) restores the round-4 behavior.  The gather
    never fires before the first sharded chunk has run (round-6 fix,
    ADVICE r05): a sparse input whose whole window already fits the
    gather budget would otherwise bypass the mesh at round 0 and run
    the ENTIRE reduce replicated on every worker.

    **Sharded tail (round-6, VERDICT r05 item 3).**  The round-5 tail
    was replicated: W-1 chips re-derived the identical plateau chain
    collapse, so per-chip tail work was CONSTANT in W — the builder's
    own scaling model capped W=8 at ~2% of north star.  With
    SHEEP_MESH_TAIL_SHARD (default on; tail_shard overrides), the
    gathered links are re-sharded by CONTIGUOUS hi vertex window
    (:func:`shard_links_by_window` — chain segments stay whole on one
    worker), each worker collapses its window's segments with LOCAL
    rounds (zero inter-chip collectives, the map-phase machinery), and
    only the converged per-window forests — a far smaller union whose
    vertices hold at most one up-link per window — re-gather for the
    replicated finish.  Per-chip tail work becomes
    O(live/W * local_rounds) + O(union) instead of O(live * rounds),
    strictly decreasing with W (measured columns in MESHBENCH).

    ``comm`` — optional dict accumulating the collective-volume model
    (per-worker logical payload bytes): sharded_global_rounds,
    pmin_payload_bytes (4(n+1) per global round), gather_payload_bytes
    (8*W*cols summed over BOTH gathers when the tail shards),
    tail_rounds (replicated, collective-free), plus the sharded-tail
    observability columns: tail_shard_rounds (local window rounds),
    tail_shard_row_live (per-chip live at the shard handoff),
    tail_gather_live / tail_finish_live (live counts entering the
    shard phase and the replicated finish).

    ``runtime`` — optional runtime.ChunkRuntime (see
    ops/forest.reduce_links_hosted): each sharded dispatch runs under the
    retry/backoff policy (halving jrounds on a fault), and — for global-f
    (reduce) phases — each chunk boundary checkpoints the link multiset
    via one all_gather (multi-process safe; the flat union of shard links
    is the complete, rung-portable build state).  Map phases (global_f
    False) get retries but no checkpoints: their per-worker partials are
    not a single multiset.  The gather-tail inherits the same runtime, so
    checkpointing continues seamlessly once the tail goes replicated.
    """
    fetch = fetch or np.asarray
    cols0 = int(lo.shape[1])
    if cols0 == 0:
        return lo, hi, 0, False
    w = mesh.size
    rounds = 0
    chunk_i = 0
    cap = int(np.ceil(np.log2(n + 2)))
    do_gather = global_f and _gather_tail_enabled(gather_tail)
    do_shard = _tail_shard_enabled(tail_shard) and w > 1
    gather_at = _gather_tail_factor() * (n + 1)
    if comm is not None:
        comm.setdefault("sharded_global_rounds", 0)
        comm.setdefault("pmin_payload_bytes", 0)
        comm.setdefault("gather_payload_bytes", 0)
        comm.setdefault("tail_rounds", 0)
        comm.setdefault("tail_shard_rounds", 0)

    def _finish_hosted(flat_lo, flat_hi, rounds):
        """Replicated single-chip finish of the gathered union."""
        from ..ops.forest import reduce_links_hosted
        flat_lo, flat_hi, _, tail_rounds, _ = reduce_links_hosted(
            flat_lo, flat_hi, n, levels=levels, jrounds=jrounds,
            first_levels=first_levels, runtime=runtime)
        if comm is not None:
            comm["tail_rounds"] += tail_rounds
        return flat_lo, flat_hi, rounds + tail_rounds, True

    while True:
        cols = int(lo.shape[1])
        # round-0 bypass guard (chunk_i >= 1): the tail rationale only
        # applies AFTER the mass-kill — see the docstring
        if do_gather and chunk_i >= 1 and w * cols <= gather_at:
            flat_lo, flat_hi = gather_links_replicated(lo, hi, mesh)
            if comm is not None:
                comm["gather_payload_bytes"] += 8 * w * cols
                comm["tail_gather_live"] = int(fetch(jnp.sum(
                    flat_lo != jnp.int32(n), dtype=jnp.int32)))
            if not do_shard:
                return _finish_hosted(flat_lo, flat_hi, rounds)
            # sharded tail: window the union, collapse each window's
            # chain segments locally (zero collectives), then gather
            # the much smaller per-window forests for the finish
            slo, shi = shard_links_by_window(flat_lo, flat_hi, n, mesh)
            if comm is not None:
                rl = [int(x) for x in fetch(row_live_counts(slo, n, mesh))]
                comm["tail_shard_row_live"] = rl
            # local rounds are capped: the cheap parallel work (star
            # collapse + short segments) lands in the first ~dozen
            # rounds; a window's long-chain crawl is exactly what the
            # replicated finish's plateau assist resolves best, so past
            # the cap the remaining links just move on
            slo, shi, local_rounds, _ = reduce_links_sharded(
                slo, shi, n, mesh, global_f=False, levels=levels,
                jrounds=jrounds, first_levels=first_levels, fetch=fetch,
                runtime=runtime, max_rounds=_tail_shard_local_rounds())
            rounds += local_rounds
            if comm is not None:
                comm["tail_shard_rounds"] += local_rounds
            fcols = int(slo.shape[1])
            flat_lo, flat_hi = gather_links_replicated(slo, shi, mesh)
            if comm is not None:
                comm["gather_payload_bytes"] += 8 * w * fcols
                comm["tail_finish_live"] = int(fetch(jnp.sum(
                    flat_lo != jnp.int32(n), dtype=jnp.int32)))
            return _finish_hosted(flat_lo, flat_hi, rounds)
        j = _SCHEDULE[chunk_i] if chunk_i < len(_SCHEDULE) else jrounds
        if max_rounds is not None:
            j = max(1, min(j, max_rounds - rounds))
        if global_f:
            # reduce rounds: flat base depth — the MESHBENCH rerun
            # measured the deep tier consistently 8-10% WORSE here with
            # unchanged round counts (deeper tables add gather cost but
            # merge chains are short enough that rounds don't drop)
            lv = min(levels, cap)
        else:
            # map rounds: same escalation as the hosted twin (PERF_NOTES
            # round-4 A/B: 1.85x at 2^22), tiered on the array width
            lv = _depth_tier(cols, cols0,
                             chunk_i < len(_SCHEDULE),
                             levels, first_levels, cap)
        if runtime is not None:
            # memory budget (ISSUE 5): jump-table depth tracks headroom
            lv = runtime.cap_levels(lv, n)
        if runtime is None:
            lo, hi, stats = chunk_sharded(lo, hi, n, mesh, lv, j, global_f)
        else:
            (lo, hi, stats), j = runtime.dispatch(
                "mesh_chunk",
                lambda jj: chunk_sharded(lo, hi, n, mesh, lv, jj, global_f),
                j)
        rounds += j
        chunk_i += 1
        if comm is not None and global_f:
            comm["sharded_global_rounds"] += j
            comm["pmin_payload_bytes"] += j * 4 * (n + 1)
        moved_i, live_i = (int(x) for x in fetch(stats))  # one sync
        # flight recorder: the mesh loop's per-chunk record (same shape
        # as the hosted loop's "reduce.chunk" — one rollup code path)
        _obs.event("reduce.chunk", live=live_i, moved=moved_i,
                   rounds=rounds, mesh=True)
        if moved_i == 0:
            return lo, hi, rounds, False
        if max_rounds is not None and rounds >= max_rounds:
            # bounded phase (the sharded tail's local pass): the caller
            # finishes elsewhere — returning unconverged is sound, every
            # chunk output has the input's threshold connectivity
            return lo, hi, rounds, False
        target = _pad_pow2_cols(live_i)
        if target <= int(lo.shape[1]) // 2:
            lo, hi = lo[:, :target], hi[:, :target]
        if runtime is not None and global_f:
            # chunk boundary: the flat union of shard links is the
            # complete resumable state (rung-portable — see driver.py)
            def _mesh_links(lo=lo, hi=hi):
                flat_lo, flat_hi = gather_links_replicated(lo, hi, mesh)
                l, h = fetch(flat_lo), fetch(flat_hi)
                keep = l < n
                return l[keep], h[keep]
            runtime.boundary(rounds, _mesh_links)


def _extract_parent(lo, hi, n: int, mesh, gathered: bool):
    """Parent extraction for either reduce_links_sharded outcome: the
    gather-tail's replicated links take the single-chip scatter-min
    (identical on every worker, no collective — the comm model's final
    parent pmin term drops); sharded links take the pmin-combined
    extraction.  One helper so the one-shot build and the streaming fold
    cannot drift."""
    if gathered:
        from ..ops.forest import parent_from_links
        return parent_from_links(lo, hi, n)
    return parent_sharded(lo, hi, n, mesh)


def build_links_chunked_sharded(tail_2d, head_2d, n: int, mesh,
                                pos=None, fetch=None, timings=None,
                                unified: bool = True,
                                gather_tail: bool | None = None,
                                tail_shard: bool | None = None,
                                comm: dict | None = None, runtime=None):
    """Full chunked mesh build from staged [W, B] edge arrays.

    Returns (seq, pos, m, parent, pst) — all replicated device arrays,
    parent [n] int32 with n marking roots.  ``timings``: optional dict
    that receives wall-clock seconds for the prep/map/reduce phases and
    the per-phase round counts (the MESHBENCH instrumentation hook).
    ``gather_tail``/``tail_shard``/``comm``: see reduce_links_sharded
    (the ICI-honest tail handoff, the round-6 per-chip tail sharding,
    and their collective-volume accounting).

    ``unified`` (default): run global-f rounds from the FIRST round —
    measured 1.77x (W=2) to 2.07x (W=8) faster than the map-then-reduce
    split at 2^18 on the virtual mesh (MESHBENCH_r04.json, the committed
    run of record), bit-identical parents, because
    the unified fixpoint converges in the same round count as the
    split's reduce phase alone: with the globally combined jump table
    available every round, the per-shard local map phase is redundant
    work.  The split form (unified=False) remains for measurement and
    because it IS the reference's transportable-partials contract — the
    map-only path (per-worker partial trees for the file-path
    tournament) still uses local rounds by construction.
    """
    import time as _time
    fetch = fetch or np.asarray
    t0 = _time.perf_counter()
    if pos is None:
        seq, pos_r, m, lo, hi, pst = prep_sharded(tail_2d, head_2d, n, mesh)
    else:
        seq, pos_r, m, lo, hi, pst = prep_sharded(
            tail_2d, head_2d, n, mesh, pos=pos, with_pos=True)
    jax.block_until_ready(lo)
    t1 = _time.perf_counter()
    if unified:
        lo, hi, red_rounds, gathered = reduce_links_sharded(
            lo, hi, n, mesh, global_f=True, fetch=fetch,
            gather_tail=gather_tail, tail_shard=tail_shard, comm=comm,
            runtime=runtime)
        map_rounds = 0
        t2 = t1
    else:
        # map: shards reduce independently to per-worker partial forests
        lo, hi, map_rounds, _ = reduce_links_sharded(
            lo, hi, n, mesh, global_f=False, fetch=fetch, runtime=runtime)
        jax.block_until_ready(lo)
        t2 = _time.perf_counter()
        # reduce: global-f rounds stitch the partials into one forest
        lo, hi, red_rounds, gathered = reduce_links_sharded(
            lo, hi, n, mesh, global_f=True, fetch=fetch,
            gather_tail=gather_tail, tail_shard=tail_shard, comm=comm,
            runtime=runtime)
    parent = _extract_parent(lo, hi, n, mesh, gathered)
    jax.block_until_ready(parent)
    t3 = _time.perf_counter()
    if timings is not None:
        timings.update(prep_s=t1 - t0, map_s=t2 - t1, reduce_s=t3 - t2,
                       map_rounds=map_rounds, reduce_rounds=red_rounds,
                       unified=unified)
    return seq, pos_r, m, parent, pst


def stage_edges_2d(tail, head, n: int, mesh, block: int | None = None):
    """Host edges -> [W, B] sharded int32 device arrays (pad with n)."""
    w = mesh.size
    e = len(tail)
    b = block if block is not None else (e + w - 1) // w
    b = max(1, b)
    t = np.full((w, b), n, dtype=np.int32)
    h = np.full((w, b), n, dtype=np.int32)
    flat_t = np.asarray(tail)
    flat_h = np.asarray(head)
    for i in range(w):
        sl = slice(i * b, min((i + 1) * b, e))
        k = max(0, sl.stop - sl.start)
        if k:
            t[i, :k] = flat_t[sl]
            h[i, :k] = flat_h[sl]
    sharding = NamedSharding(mesh, P(AXIS, None))
    if jax.process_count() == 1:
        return jax.device_put(t, sharding), jax.device_put(h, sharding)
    mk = jax.make_array_from_callback
    return (mk(t.shape, sharding, lambda idx: t[idx]),
            mk(h.shape, sharding, lambda idx: h[idx]))


@functools.partial(jax.jit, static_argnames=("n", "cn", "mesh"))
def prep_stream_sharded(parent, tail, head, pos, n: int, cn: int, mesh):
    """One streamed block's links + the carry forest's links, sharded.

    parent int32 [n] replicated (n marks roots); tail/head int32 [W, B]
    sharded vid records (pad with values >= len(pos)-1); pos the
    vid->position table with a sentinel slot at the end.  The carry forest
    re-enters as its (kid -> parent) links, SHARDED: worker i owns carry
    rows [i*cn, (i+1)*cn) — any shard may host any link, so splitting the
    carry over the axis keeps per-worker state O(n/W + B) for the link
    arrays.  Returns (lo, hi [W, B+cn] sharded, pst_delta [n] replicated).
    """
    def body(parent, t, h, posr):
        sent = jnp.int32(n)
        vid_cap = jnp.int32(posr.shape[0] - 1)
        pt = posr[jnp.minimum(t[0].astype(jnp.int32), vid_cap)]
        ph = posr[jnp.minimum(h[0].astype(jnp.int32), vid_cap)]
        lo = jnp.minimum(pt, ph)
        hi = jnp.maximum(pt, ph)
        pst_local = pst_weights(jnp.where(lo == hi, sent, lo), n)
        dead = (lo >= hi) | (hi >= sent)
        lo = jnp.where(dead, sent, lo)
        hi = jnp.where(dead, sent, hi)
        # carry shard: this worker's slice of the forest's links
        i = lax.axis_index(AXIS)
        base = i.astype(jnp.int32) * jnp.int32(cn)
        kid = base + jnp.arange(cn, dtype=jnp.int32)
        in_range = kid < jnp.int32(n)
        cpar = lax.dynamic_slice(
            jnp.concatenate([parent.astype(jnp.int32),
                             jnp.full((cn,), sent, jnp.int32)]),
            (base,), (cn,))
        clive = in_range & (cpar < sent)
        clo = jnp.where(clive, kid, sent)
        chi = jnp.where(clive, cpar, sent)
        lo = jnp.concatenate([clo, lo])
        hi = jnp.concatenate([chi, hi])
        return lo[None, :], hi[None, :], lax.psum(pst_local, AXIS)

    fn = shard_map(body, mesh=mesh,
                   in_specs=(P(), P(AXIS, None), P(AXIS, None), P()),
                   out_specs=(P(AXIS, None), P(AXIS, None), P()),
                   check_vma=False)
    return fn(parent, tail, head, pos)


def build_graph_streaming_chunked(blocks, n: int, pos: np.ndarray,
                                  block_edges: int,
                                  num_workers: int | None = None):
    """OOM streaming over the mesh with bounded dispatches only.

    Same contract as parallel.stream.build_graph_streaming_sharded —
    (Forest over n positions, total_rounds) — but each block folds through
    the chunked sharded reducer (unified global-f rounds; see
    build_links_chunked_sharded for why the local map phase is redundant
    work) instead of an in-jit while_loop fixpoint.  The carry forest
    re-enters sharded, so worker-resident link state stays O(n/W + B/W)
    per block.
    """
    from .. import INVALID_JNID
    from ..core.forest import Forest
    from ..ops.stream import _full_vid_pos
    from .build import _fetch

    mesh = make_mesh(num_workers)
    w = mesh.size
    block_pad = max(w, ((block_edges + w - 1) // w) * w)
    b = block_pad // w
    cn = (n + w - 1) // w
    repl = NamedSharding(mesh, P())
    shard2d = NamedSharding(mesh, P(AXIS, None))

    def put(x, sharding):
        if jax.process_count() == 1:
            return jax.device_put(x, sharding)
        return jax.make_array_from_callback(
            x.shape, sharding, lambda idx: x[idx])

    pos_d = put(_full_vid_pos(pos, n).astype(np.int32), repl)
    vid_pad = len(pos)  # pad records map to the table's sentinel slot
    parent = put(np.full(n, n, dtype=np.int32), repl)
    pst = np.zeros(n, dtype=np.int64)
    total_rounds = 0
    for tail, head in blocks:
        k = len(tail)
        if k > w * b:
            raise ValueError(
                f"streamed block of {k} edges exceeds block_edges="
                f"{block_edges} (padded capacity {w * b})")
        t = np.full((w, b), vid_pad, dtype=np.int32)
        h = np.full((w, b), vid_pad, dtype=np.int32)
        for i in range(w):
            sl = slice(i * b, min((i + 1) * b, k))
            cnt = max(0, sl.stop - sl.start)
            if cnt:
                t[i, :cnt] = tail[sl]
                h[i, :cnt] = head[sl]
        lo, hi, pst_delta = prep_stream_sharded(
            parent, put(t, shard2d), put(h, shard2d), pos_d, n, cn, mesh)
        # unified global-f rounds from the start (see
        # build_links_chunked_sharded: the split's local map phase is
        # redundant when the combined jump table is available per round)
        lo, hi, r, gathered = reduce_links_sharded(lo, hi, n, mesh,
                                                   global_f=True,
                                                   fetch=_fetch)
        parent = _extract_parent(lo, hi, n, mesh, gathered)
        # int64 host accumulation: per-block deltas are int32-safe, the
        # running sum follows the uint32 weight contract via the final cast
        pst += _fetch(pst_delta).astype(np.int64)
        total_rounds += r
    parent_np = _fetch(parent).astype(np.int64)
    out = np.full(n, INVALID_JNID, dtype=np.uint32)
    live = parent_np < n
    out[live] = parent_np[live].astype(np.uint32)
    return Forest(out, (pst & 0xFFFFFFFF).astype(np.uint32)), total_rounds


def _stage_inputs(tail, head, num_vertices, num_workers, seq):
    """Shared host-facing prologue of the chunked wrappers: mesh, vertex
    count inference, edge staging, and the given-seq position-table
    device staging (including the multi-process make_array branch —
    kept in ONE place so a fix cannot drift between the merge and map
    wrappers).  Returns (mesh, n, t2d, h2d, pos_d); n == 0 signals the
    empty graph (arrays None then), pos_d is None when seq is None.
    """
    mesh = make_mesh(num_workers)
    n = num_vertices
    if n is None:
        n = int(max(tail.max(initial=0), head.max(initial=0))) + 1 \
            if len(tail) else 0
    if seq is not None and len(seq):
        n = max(n, int(seq.max()) + 1)
    if n == 0:
        return mesh, 0, None, None, None
    t2d, h2d = stage_edges_2d(tail, head, n, mesh)
    # where the edge shards really sit (not all on device 0)
    _obs.event("mesh.stage", workers=mesh.size,
               devices=len(t2d.sharding.device_set))
    pos_d = None
    if seq is not None:
        from ..core.sequence import sequence_positions
        pos_np = sequence_positions(seq, n - 1).astype(np.int64)
        sharding = NamedSharding(mesh, P())
        pos_d = jax.device_put(pos_np.astype(np.int32), sharding) \
            if jax.process_count() == 1 else jax.make_array_from_callback(
                pos_np.shape, sharding,
                lambda idx: pos_np.astype(np.int32)[idx])
    return mesh, n, t2d, h2d, pos_d


def map_graph_chunked_distributed(tail, head, num_vertices=None,
                                  num_workers=None, seq=None):
    """Map-only chunked mesh build: (seq uint32 [m], [Forest] * W).

    The bounded-dispatch twin of parallel.build.map_graph_distributed
    (`-i` without `-r`): each worker's edge shard reduces with LOCAL
    chunk rounds only (reduce_links_sharded global_f=False) to a partial
    forest over the shared sequence, ready for the file-path merge
    tournament.  Per-worker pst counts only that shard's edges
    (graph2tree.cpp:148 rank-suffixed saves semantics).
    """
    from .build import _fetch, _to_forest

    mesh, n, t2d, h2d, pos_d = _stage_inputs(
        tail, head, num_vertices, num_workers, seq)
    if n == 0:
        return np.empty(0, np.uint32), []
    if pos_d is None:
        dseq, _, m, lo, hi, psts = prep_sharded(t2d, h2d, n, mesh,
                                                local_pst=True)
        m = int(_fetch(m))
        out_seq = _fetch(dseq)[:m].astype(np.uint32)
    else:
        dseq, _, m, lo, hi, psts = prep_sharded(
            t2d, h2d, n, mesh, pos=pos_d, with_pos=True, local_pst=True)
        m = len(seq)
        out_seq = np.asarray(seq, dtype=np.uint32)
    lo, hi, _, _ = reduce_links_sharded(lo, hi, n, mesh, global_f=False,
                                        fetch=_fetch)
    parents = _fetch(parent_sharded_local(lo, hi, n, mesh))
    psts_np = _fetch(psts)
    return out_seq, [_to_forest(parents[i], psts_np[i], n, m)
                     for i in range(mesh.size)]


def build_graph_chunked_distributed(tail, head, num_vertices=None,
                                    num_workers=None, seq=None,
                                    timings=None):
    """Host-facing chunked mesh build: (seq uint32 [m], Forest over m).

    Same contract as parallel.build.build_graph_distributed, but every
    device dispatch is bounded — the execution shape real hardware needs.
    """
    from ..core.forest import Forest
    from .build import _fetch, _to_forest

    mesh, n, t2d, h2d, pos_d = _stage_inputs(
        tail, head, num_vertices, num_workers, seq)
    if n == 0:
        return (np.empty(0, np.uint32),
                Forest(np.empty(0, np.uint32), np.empty(0, np.uint32)))
    if timings is None:
        timings = {}
    dseq, _, m, parent, pst = build_links_chunked_sharded(
        t2d, h2d, n, mesh, pos=pos_d, fetch=_fetch, timings=timings)
    _obs.event("mesh.build", **timings)
    if seq is None:
        m = int(_fetch(m))
        out_seq = _fetch(dseq)[:m].astype(np.uint32)
    else:
        m = len(seq)
        out_seq = np.asarray(seq, dtype=np.uint32)
    return out_seq, _to_forest(_fetch(parent), _fetch(pst), n, m)
