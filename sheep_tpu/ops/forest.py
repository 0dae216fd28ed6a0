"""Elimination-forest construction as a fixed-shape XLA fixpoint kernel.

The reference builds the forest with an inherently sequential pointer-chasing
loop: stream vertices in sequence order, union-find re-adoption per edge
(lib/jtree.cpp:34-55, lib/unionfind.h:78-102).  A line-for-line port would be
a latency-bound scalar loop — the worst possible TPU program.  This module
replaces it with a bandwidth-bound batched algorithm based on a structural
fact about the tree itself:

    The elimination forest is the single-linkage merge hierarchy of the
    position graph under edge weight w({lo,hi}) = hi.  Proof sketch: run
    Kruskal ascending by weight.  Every edge of weight h is incident on h,
    and all earlier edges have both endpoints < h, so at the moment weight-h
    edges are processed, h is the maximum of its component and every
    component adjacent to h via a weight-h edge has some maximum r < h.
    Merging assigns parent[r] = h — exactly the reference's
    ``adopt(root(nbr), X)`` step (lib/jnode.h:158-162).  Hence the forest is
    a function of *threshold connectivity* only: any edge-multiset transform
    that preserves, for every t, the connected components of the subgraph of
    edges with weight <= t, preserves the forest.

One transform suffices, iterated to fixpoint over static-shape int32 edge
arrays (dead edges parked at a sentinel so shapes never change):

  T   bounded pointer jump.  With f(v) = v's current minimum up-neighbor
      (one scatter-min over the live edges), relabel an edge (lo, hi) to
      (f^k(lo), hi) for the largest k with f^k(lo) < hi: lo and f^k(lo)
      are already connected at threshold f^k(lo) < hi, so threshold
      connectivity is preserved.  Values along an f-chain are strictly
      increasing, so the maximal ancestor below hi is found by binary
      lifting — square f into ancestor tables f^2, f^4, ... and take
      strides greedily from the largest down.  (Self-loops and duplicates
      need no special handling: they rewrite like any edge and never
      perturb the scatter-min.)

Every applied rewrite strictly increases some live-edge ``lo`` field and
``lo`` is bounded by n, so the iteration terminates unconditionally — the
loop runs until no edge moves, no round cap needed.  At the fixpoint every
live edge (lo, hi) has f(lo) >= hi, and f(lo) <= hi by definition of f, so
hi == f(lo): each vertex has at most one distinct up-neighbor, the edge set
*is* a functional forest, and that forest is its own merge hierarchy — i.e.
the answer.  ``parent[v]`` is then just a scatter-min of hi by lo.
``pst_weight`` is order-free (one count per non-loop edge at its lower
endpoint, lib/jtree.cpp:47-49) and is a single segment-sum over the
*original* links.

An earlier revision also rewrote hub stars into chains with a per-round
lexicographic ``lax.sort``; the jump transform alone reaches the same
fixpoint (measured: identical parents, ~20% more rounds) and a sort-free
round is ~5x cheaper, since it is all gathers and scatter-mins.  The
lifting depth per round is capped (``jump_levels``, default 6 → jumps up
to 2^5 per round): deeper tables barely reduce the round count on
power-law graphs but pay ``levels`` extra gathers every round.

The same kernel implements the distributed tree merge (lib/jnode.cpp:174-250,
the MPI_Reduce custom op): a partial forest re-enters as its (kid, parent)
link set, and merging k partials is rebuilding from their concatenated links
— associativity for free, which sheep_tpu.parallel exploits over the mesh.
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from .. import INVALID_JNID
from ..core.forest import Forest
from ..obs import trace as _obs

_I32_MAX = np.int32(np.iinfo(np.int32).max)

#: lifting depth per round — jumps advance up to 2^(levels-1) ancestors
_JUMP_LEVELS = 6


def _pack64_sorts() -> bool:
    """Trace-time gate for the packed single-key link sort.

    SHEEP_SORT_PACK64=1/0 forces it; unset defaults to on for the cpu
    backend (measured 4.2x vs the 2-key variadic sort at 2^20-2^22 —
    XLA:CPU's variadic sort carries every operand through a slow generic
    comparator loop, while a single s64 key hits the fast radix path)
    and off for accelerators, where s64 is emulated in 32-bit lanes and
    the trade needs an on-chip A/B before it can be the default.

    Caveat: the decision reads the DEFAULT backend at trace time.  Host-side work pinned to CPU via
    jax.default_device while an accelerator is the default backend gets
    the 2-key branch; set SHEEP_SORT_PACK64=1 explicitly there.
    """
    import os
    v = os.environ.get("SHEEP_SORT_PACK64", "")
    if v in ("0", "1"):
        return v == "1"
    return jax.devices()[0].platform == "cpu"


def sort_links(lo: jnp.ndarray, hi: jnp.ndarray):
    """Lexicographic (lo, hi) sort of int32 link arrays.

    When :func:`_pack64_sorts` allows, packs each pair into one int64
    ((lo << 32) | hi — exact for the package-wide nonnegative-int32
    value contract, sentinels included) and sorts a single key; the
    scoped ``jax.enable_x64`` keeps the wider dtype local to these few
    ops even under a jit trace of an otherwise-x32 program.
    """
    if _pack64_sorts():
        with jax.enable_x64():
            # pure-lax packing: convert_element_type + same-shape lax bit
            # ops keep every operand i64 inside the scoped x64 context,
            # even when tracing under an outer x32 jit
            def i64(x):
                return lax.convert_element_type(x, jnp.int64)
            shift = i64(jnp.full(lo.shape, 32, jnp.int32))
            mask = i64(jnp.full(lo.shape, 0xFFFFFFFF, jnp.uint32))
            key = lax.bitwise_or(lax.shift_left(i64(lo), shift), i64(hi))
            key = lax.sort(key)
            # values are nonnegative (package-wide int32 contract), so the
            # logical right shift recovers lo exactly
            return (lax.convert_element_type(
                        lax.shift_right_logical(key, shift), jnp.int32),
                    lax.convert_element_type(
                        lax.bitwise_and(key, mask), jnp.int32))
    return lax.sort((lo, hi), num_keys=2)


def sort_links_by_hi(lo: jnp.ndarray, hi: jnp.ndarray):
    """Sort the link table by ASCENDING hi (lo tie break; dead sentinel
    pairs last) — the streaming windowed handoff's device-side
    windowing: contiguous equal-count slices of the result ARE the
    hi-quantile windows (the parallel.chunked.hi_window_bounds rule),
    arriving in exactly the order the resumable native fold consumes.
    Same pack64 policy as :func:`sort_links` with the roles swapped
    ((hi << 32) | lo).
    """
    if _pack64_sorts():
        with jax.enable_x64():
            def i64(x):
                return lax.convert_element_type(x, jnp.int64)
            shift = i64(jnp.full(lo.shape, 32, jnp.int32))
            mask = i64(jnp.full(lo.shape, 0xFFFFFFFF, jnp.uint32))
            key = lax.bitwise_or(lax.shift_left(i64(hi), shift), i64(lo))
            key = lax.sort(key)
            return (lax.convert_element_type(
                        lax.bitwise_and(key, mask), jnp.int32),
                    lax.convert_element_type(
                        lax.shift_right_logical(key, shift), jnp.int32))
    hi_s, lo_s = lax.sort((hi, lo), num_keys=2)
    return lo_s, hi_s


def _rewrite_sorted(lo: jnp.ndarray, hi: jnp.ndarray, n: int):
    """Star -> chain rewrite + dedupe on SORTED (lo, hi) arrays.  For a
    vertex v with up-neighbors h1 < h2 < ... < hk, rewrites edges
    (v,h2..hk) to (h1,h2), (h2,h3), ... — at any threshold t the connected
    set {v} + {hj <= t} is unchanged; exact duplicates die.  Returns
    (lo, hi, applied_count)."""
    sent = jnp.int32(n)
    prev_same = jnp.concatenate(
        [jnp.zeros((1,), jnp.bool_), lo[1:] == lo[:-1]])
    prev_hi = jnp.concatenate([jnp.full((1,), sent, jnp.int32), hi[:-1]])
    applied = prev_same & (lo != sent)
    lo = jnp.where(applied, prev_hi, lo)
    # prev_hi <= hi inside a sorted group; equality = duplicate edge, dead.
    dead = lo >= hi
    lo = jnp.where(dead, sent, lo)
    hi = jnp.where(dead, sent, hi)
    return lo, hi, jnp.sum(applied, dtype=jnp.int32)


def _lift_descend(lo: jnp.ndarray, hi: jnp.ndarray, n: int, levels: int,
                  f: jnp.ndarray):
    """Binary-lifting descent through a GIVEN one-step table f [n+1]:
    square f into ancestor tables and greedily advance each lo to its
    maximal f-ancestor strictly below hi.  Returns (lo, moved_count).

    Taking f as a parameter lets the mesh path combine per-shard tables
    (lax.pmin) before lifting.
    """
    lo_in = lo
    tables = [f]
    for _ in range(levels - 1):
        tables.append(tables[-1][tables[-1]])
    for table in reversed(tables):
        nlo = table[lo]
        lo = jnp.where(nlo < hi, nlo, lo)
    return lo, jnp.sum(lo != lo_in, dtype=jnp.int32)


def _jump(lo: jnp.ndarray, hi: jnp.ndarray, n: int, levels: int):
    """Binary-lifted pointer jump: advance each lo to its maximal
    f-ancestor strictly below hi, where f = min up-neighbor over the live
    links (slot n absorbs sentinels).  Returns (lo, moved_count)."""
    sent = jnp.int32(n)
    f = jnp.full(n + 1, sent, jnp.int32).at[lo].min(hi)
    return _lift_descend(lo, hi, n, levels, f)


def _sort_step(lo: jnp.ndarray, hi: jnp.ndarray, n: int):
    """Sort + star->chain rewrite (the while_loop kernel's accelerator; a
    pure jump round discovers a hub's chain only one link per round)."""
    lo, hi = sort_links(lo, hi)
    lo, hi, _ = _rewrite_sorted(lo, hi, n)
    return lo, hi


def _round_step(lo: jnp.ndarray, hi: jnp.ndarray, do_sort: jnp.ndarray,
                n: int, levels: int):
    """One jump round (+ sort rewrite when ``do_sort``).  Dead edges sit
    at n.  Returns (lo, hi, moved) where ``moved`` counts edges whose lo
    advanced this round; the caller loops while moved > 0 and schedules
    ``do_sort`` at exponentially spaced round indices."""
    lo, hi = lax.cond(do_sort,
                      lambda args: _sort_step(*args, n=n),
                      lambda args: args, (lo, hi))
    lo, moved = _jump(lo, hi, n, levels)
    return lo, hi, moved


@functools.partial(jax.jit, static_argnames=("n", "jump_levels"))
def forest_fixpoint(lo: jnp.ndarray, hi: jnp.ndarray, n: int,
                    jump_levels: int | None = None):
    """Parent array of the elimination forest of links (lo -> hi), lo < hi.

    Inputs are int32 position pairs; entries with lo == hi == n are ignored
    (sentinels), which is how self-loops and padding are passed in.  Returns
    (parent int32 [n] with n marking roots, rounds int32).  The loop runs
    until no edge moves — termination is guaranteed because every applied
    rewrite strictly increases a lo field bounded by n.
    """
    sent = jnp.int32(n)
    if jump_levels is None:
        # Elimination-tree depth grows roughly with sqrt-to-log factors of
        # n on power-law graphs; measured sweet spots: 6 at n=2^16, 8 at
        # n=2^18.  Deeper tables barely cut rounds but cost per round.
        jump_levels = max(_JUMP_LEVELS, int(np.ceil(np.log2(n + 2))) // 2)
    levels = max(1, min(jump_levels, int(np.ceil(np.log2(n + 2)))))

    if lo.shape[0] == 0:
        return jnp.full((n,), sent, jnp.int32), jnp.int32(0)

    def cond(state):
        _, _, moved, _ = state
        return moved > 0

    def body(state):
        lo, hi, _, rounds = state
        # Sort accelerator at exponentially spaced rounds (7, 15, 31, ...):
        # a hub star otherwise unrolls only one chain link per jump round,
        # and O(log) sorts bound that worst case without paying a sort
        # every round.  Exiting on moved == 0 is always sound — the jump
        # fixpoint alone already implies a functional forest.
        do_sort = (rounds >= 7) & ((rounds & (rounds + 1)) == 0)
        lo, hi, moved = _round_step(lo, hi, do_sort, n, levels)
        return lo, hi, moved, rounds + 1

    lo = lo.astype(jnp.int32)
    hi = hi.astype(jnp.int32)
    # Initial 'moved' must inherit lo's varying manual axes so the carry
    # types line up when this runs inside shard_map; the max is >= 1 for a
    # nonempty array, so the first round always runs.
    state = (lo, hi, jnp.maximum(jnp.max(lo), 1), jnp.int32(0))
    lo, hi, _, rounds = lax.while_loop(cond, body, state)
    parent = jnp.full(n + 1, sent, jnp.int32).at[lo].min(hi)[:n]
    return parent, rounds


# ---------------------------------------------------------------------------
# Host-orchestrated chunked fixpoint — the production path on real hardware.
#
# The single-dispatch while_loop kernel above is correct, but its device
# time grows with the data-dependent round count, and the win comes from
# shrinking the arrays, not the op count: one sort round kills 85-93% of
# the edges (duplicates + star collapse) within 2-4 rounds.
#
# The chunked driver therefore runs J rounds per dispatch with a
# data-independent fori_loop (bounded execution time, no faults), reads the
# live count between chunks, and re-dispatches on sliced arrays.  Measured
# round structure (scripts/round_proto.py): sort every round + 10-level
# lifting converges in ~30 rounds at 2^18 vs 42 for the exponential-sort
# schedule, and live edges drop to ~15% of E by round 2.
# ---------------------------------------------------------------------------


def _chunk_round(lo, hi, n: int, levels: int):
    """One production round: sort -> chain rewrite -> L-level jump.

    Returns (lo, hi, moved, live) where ``live`` counts non-sentinel edges
    right after the sort — the tail beyond it is dead in the *output* too
    (rewrites never resurrect an edge), which is what makes host-side
    slicing sound.
    """
    sent = jnp.int32(n)
    lo, hi = sort_links(lo, hi)
    live = jnp.sum(lo != sent, dtype=jnp.int32)
    lo, hi, rewrites = _rewrite_sorted(lo, hi, n)
    lo, jumped = _jump(lo, hi, n, levels)
    return lo, hi, rewrites + jumped, live


@functools.partial(jax.jit, static_argnames=("n", "levels", "jrounds"))
def fixpoint_chunk(lo: jnp.ndarray, hi: jnp.ndarray, n: int,
                   levels: int, jrounds: int):
    """``jrounds`` chunk rounds in one dispatch (data-independent fori_loop).

    Returns (lo, hi, stats) with stats = int32 [2] of
    (moved_last_round, live_after_last_sort) — stacked so the host reads
    both in ONE transfer: one host sync per chunk, not two.
    """
    def body(_, st):
        lo, hi, _, _ = st
        return _chunk_round(lo, hi, n, levels)

    state = (lo.astype(jnp.int32), hi.astype(jnp.int32),
             jnp.int32(0), jnp.int32(lo.shape[0]))
    lo, hi, moved, live = lax.fori_loop(0, jrounds, body, state)
    return lo, hi, jnp.stack([moved, live])


@functools.partial(jax.jit, static_argnames=("n", "levels"))
def jump_chunk(lo: jnp.ndarray, hi: jnp.ndarray, n: int, levels: int):
    """One jump-only round (no sort): a cheap opener for full-size arrays.

    Round 1's sort retires only ~6% of a power-law edge set (exact input
    duplicates); the mass kill needs jump-induced lo collisions FIRST,
    which the next round's sort then dedupes.  Skipping the opener's sort
    was measured 26% faster to the hybrid handoff at 2^18 on the cpu
    backend (scripts/sched_ab.py).  Returns (lo, hi, stats) like
    :func:`fixpoint_chunk`, but with NO sort the returned ``live`` count
    carries no prefix guarantee — live edges may sit anywhere in the
    arrays, so callers must NOT compact on it (it is an upper bound on
    the live population only, sound because the jump never resurrects a
    dead edge).
    """
    sent = jnp.int32(n)
    lo = lo.astype(jnp.int32)
    hi = hi.astype(jnp.int32)
    live = jnp.sum(lo != sent, dtype=jnp.int32)
    lo, moved = _jump(lo, hi, n, levels)
    return lo, hi, jnp.stack([moved, live])


@jax.jit
def pack_links_6b(lo: jnp.ndarray, hi: jnp.ndarray) -> jnp.ndarray:
    """Pack (lo, hi) int32 pairs with values < 2^24 into uint8 [k, 6].

    24-bit little-endian halves cut the handoff fetch's bytes 25% vs
    two int32 arrays.  Sentinel values (== n) pack fine: n < 2^24 at
    every supported size, and the host filters lo < n after unpack.
    """
    lo = lo.astype(jnp.int32)
    hi = hi.astype(jnp.int32)
    return jnp.stack(
        [lo & 0xFF, (lo >> 8) & 0xFF, (lo >> 16) & 0xFF,
         hi & 0xFF, (hi >> 8) & 0xFF, (hi >> 16) & 0xFF],
        axis=1).astype(jnp.uint8)


def unpack_links_6b(buf: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Host-side inverse of :func:`pack_links_6b` (numpy, vectorized)."""
    b = buf.astype(np.int32)
    lo = b[:, 0] | (b[:, 1] << 8) | (b[:, 2] << 16)
    hi = b[:, 3] | (b[:, 4] << 8) | (b[:, 5] << 16)
    return lo, hi


@functools.partial(jax.jit, static_argnames=("n",))
def parent_from_links(lo: jnp.ndarray, hi: jnp.ndarray, n: int):
    """Scatter-min parent extraction (valid once links form a forest)."""
    sent = jnp.int32(n)
    return jnp.full(n + 1, sent, jnp.int32).at[lo.astype(jnp.int32)].min(
        hi.astype(jnp.int32))[:n]


def _pad_pow2(x: int, lo_cap: int = 1 << 12) -> int:
    p = lo_cap
    while p < x:
        p <<= 1
    return p


@functools.partial(jax.jit, static_argnames=("n", "nc"))
def vremap_compact(lo: jnp.ndarray, hi: jnp.ndarray, n: int, nc: int):
    """Relabel the vertices of the live links into a dense space [0, nc).

    Why: one chunk round costs O(n * levels) in jump-table work (the
    ``jnp.full(n + 1)`` fill plus ``levels - 1`` table squarings in
    :func:`_lift_descend`) no matter how few links remain — measured
    ~70ms/round at n=2^22 on the cpu backend with only 8k live links.
    Once compaction
    has shrunk the link arrays, relabeling the surviving vertices into a
    dense [0, nc) space makes every subsequent round O(links * levels).

    Soundness: the map (ascending rank of the vertex among the distinct
    live-link endpoints) is strictly monotone, so lo < hi ordering, the
    min-up-neighbor function, and threshold connectivity over the
    relabeled vertices are all preserved; the elimination forest is a
    function of threshold connectivity only (module docstring).  Every
    vertex that still needs a parent appears in some live link: rewrites
    never drop a vertex's last link (a non-root vertex's min-up link
    survives to the functional-forest fixpoint), so vertices absent from
    the live links are already settled (roots/isolated) and back-map to
    parent-less slots.

    Requires nc >= number of distinct live endpoints (callers pass
    nc = 2 * len(lo), a safe bound).  Returns (lo_c, hi_c, back) where
    back is int32 [nc + 1]: compact id -> original position, back[nc]
    (the compact sentinel) and unused slots hold n.
    """
    sent = jnp.int32(n)
    csent = jnp.int32(nc)
    lo = lo.astype(jnp.int32)
    hi = hi.astype(jnp.int32)
    verts = lax.sort(jnp.concatenate([lo, hi]))  # sentinels sort last
    is_live = verts < sent
    is_new = is_live & jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), verts[1:] != verts[:-1]])
    # every occurrence of a vertex gets the same rank (cumsum counts the
    # first occurrence only), so duplicate scatter writes agree
    rank = jnp.cumsum(is_new.astype(jnp.int32)) - 1
    fwd = jnp.full(n + 1, csent, jnp.int32).at[
        jnp.where(is_live, verts, jnp.int32(n + 1))].set(rank, mode="drop")
    back = jnp.full(nc + 1, sent, jnp.int32).at[
        jnp.where(is_live, rank, jnp.int32(nc + 1))].set(verts, mode="drop")
    return fwd[lo], fwd[hi], back


@jax.jit
def vremap_back(lo_c: jnp.ndarray, hi_c: jnp.ndarray, back: jnp.ndarray):
    """Inverse of :func:`vremap_compact` on link arrays (compact sentinel
    maps through back's last slot to the original n)."""
    return back[lo_c], back[hi_c]


def _vremap_enabled() -> bool:
    import os
    return os.environ.get("SHEEP_VREMAP", "1") != "0"


# ---------------------------------------------------------------------------
# Plateau-adaptive round scheduling (round-6).
#
# Measured trajectory of the chunk loop on power-law graphs (2^20-2^22,
# cpu backend): the mass-kill retires ~93% of the edges in 3-4 rounds,
# then the loop spends the REST of the build — 24 of 34 rounds at 2^20,
# ~80 of 90 at 2^22 — on a "plateau" where the live count barely moves and
# per-round ``moved`` decays into a tail of single digits.  Probing that
# tail shows why no lifting depth fixes it: the last movers are straggler
# links (lo, hi) whose f-chain toward hi does not EXIST yet — each round
# a straggler lands one chain position further, and that landing is what
# materializes the next f-step (f[y] := hi) for the stragglers behind it.
# Chains materialize one link per round; binary lifting cannot cross a
# chain that is not there (levels=cap was measured to cut 194 j=1 rounds
# to 83 at 2^22 and then stall in the same moved<=6 crawl for 30+ rounds).
#
# The crawl is inherently SEQUENTIAL — so the scheduler runs it
# sequentially, where sequential pointer-chasing is cheap: the host.
# Once the per-chunk stats (already fetched — no extra sync) show a
# plateau (live-count drop < 5% per chunk, or movers a <=1/8 fraction of
# live), the loop fetches the live links plus the one-step table f,
# walks every straggler's f-chain to its maximal ancestor below hi on
# the host — materializing chain steps as links land, exactly the
# device transform executed sparsely — and scatters the few advanced lo
# values back.  One walk drives the whole cascade to its fixpoint, so
# the tail collapses to ~one assist plus a j=1 verification chunk:
# measured 90 -> 13 rounds at 2^22, 34 -> 13 at 2^20, parents
# bit-identical to the oracle.  Soundness is the module-docstring
# argument unchanged: each advance moves lo to an f-ancestor strictly
# below hi (threshold connectivity preserved), and the "phantom"
# f-entries left behind by advanced links still witness real
# connectivity (the chain that carried the link there).  Every advance
# strictly increases a lo bounded by n, so termination is unchanged.
#
# SHEEP_PLATEAU_ADAPT=0 restores the round-5 schedule;
# SHEEP_PLATEAU_ASSIST_CAP bounds the stragglers walked per assist
# (default 2^17 — past it the assist defers to the escalated-depth
# device rounds until the mover count decays under the cap).
# ---------------------------------------------------------------------------


def _plateau_enabled() -> bool:
    import os
    return os.environ.get("SHEEP_PLATEAU_ADAPT", "1") != "0"


def _plateau_assist_cap() -> int:
    import os
    return int(os.environ.get("SHEEP_PLATEAU_ASSIST_CAP", str(1 << 17)))


@functools.partial(jax.jit, static_argnames=("n",))
def min_up_table(lo: jnp.ndarray, hi: jnp.ndarray, n: int) -> jnp.ndarray:
    """One-step jump table f [n+1]: min up-neighbor per vertex over the
    live links (slot n absorbs sentinels) — the assist's device-side
    half, one dispatch."""
    return jnp.full(n + 1, jnp.int32(n), jnp.int32).at[
        lo.astype(jnp.int32)].min(hi.astype(jnp.int32))


def plateau_assist_walk(l: np.ndarray, h: np.ndarray, f: np.ndarray,
                        n: int, cap: int | None = None,
                        max_passes: int = 4096) -> tuple[int, int, int]:
    """Host straggler walk: advance every live link's lo to its maximal
    f-ancestor strictly below hi, materializing chain steps (f[y] :=
    min(f[y], hi)) as links land, until no straggler remains.

    l, h, f: int64 numpy arrays (l and f are MUTATED in place); dead
    slots hold n, f[n] == n.  ``cap`` bounds the initial straggler set
    (the walk bails untouched past it — the caller's escalated device
    rounds shrink the set first).  Returns (walks, passes): total
    straggler advances and cascade passes run.

    Passes after the first are incremental: a settled link can only
    re-become a straggler when f at its CURRENT lo drops, and f only
    drops at patch points — so each pass rechecks the tracked set (every
    link that was ever a straggler) plus the untracked links whose lo
    sits at a freshly patched vertex, found through a sorted snapshot of
    the pre-walk lo values (untracked links never moved, so the snapshot
    is exact for them).  That keeps a deep cascade at O(stragglers) per
    pass instead of O(live).  Returns (walks, passes, stragglers) —
    stragglers is the initial straggler count (> cap on a bail).
    """
    sent_safe = np.minimum(l, n)
    cand = np.nonzero((l < n) & (h > f[sent_safe]))[0]
    if cand.size == 0:
        return 0, 0, 0
    if cap is not None and cand.size > cap:
        return 0, 0, int(cand.size)
    n0 = int(cand.size)
    order = np.argsort(l, kind="stable")
    l0_sorted = l[order]  # pre-walk snapshot (exact for untracked links)
    tracked_mask = np.zeros(l.shape[0], np.bool_)
    tracked_mask[cand] = True
    tracked = cand
    walks = 0
    passes = 0
    while passes < max_passes and cand.size:
        passes += 1
        ids = cand[f[l[cand]] < h[cand]]
        if ids.size == 0:
            break
        walks += int(ids.size)
        sl = l[ids]
        sh = h[ids]
        while True:  # vectorized descent; f is strictly increasing
            nx = f[sl]
            adv = nx < sh
            if not adv.any():
                break
            sl = np.where(adv, nx, sl)
        l[ids] = sl
        before = f[sl]
        np.minimum.at(f, sl, sh)
        patched = np.unique(sl[f[sl] < before])
        if patched.size:
            a = np.searchsorted(l0_sorted, patched, side="left")
            b = np.searchsorted(l0_sorted, patched, side="right")
            spans = [order[x:y] for x, y in zip(a, b) if y > x]
            if spans:
                fresh = np.concatenate(spans)
                fresh = fresh[~tracked_mask[fresh]]
                if fresh.size:
                    tracked_mask[fresh] = True
                    tracked = np.concatenate([tracked, fresh])
        cand = tracked
    return walks, passes, n0


def _pad_pow2_min(x: int, floor: int = 16) -> int:
    p = floor
    while p < x:
        p <<= 1
    return p


@functools.partial(jax.jit, static_argnames=("k",))
def _scatter_lo(lo: jnp.ndarray, idx: jnp.ndarray, vals: jnp.ndarray,
                k: int):
    """Scatter ``k`` advanced lo values back into the device array.
    idx/vals are padded to k with idx == len(lo) (dropped), so the
    compile family stays bounded at one program per (width, k-pow2)."""
    return lo.at[idx].set(vals, mode="drop")


class _PlateauSched:
    """Sticky plateau detector + assist driver for the hosted chunk loop.

    Consumes the (moved, live) stats the loop already fetches; once the
    plateau is on, the loop escalates lifting depth to the full cap and
    shrinks the chunk length to j=1 verification rounds around host
    assists.  ``assist`` runs the straggler walk on fetched state and
    scatters advanced lo values back (bounded: cap stragglers, one
    width-sized f fetch, walks-sized h2d)."""

    #: live-count drop per chunk under which the loop is plateaued
    RATIO = 0.95
    #: movers at most this fraction of live also signal the plateau
    MOVED_FRAC = 8

    def __init__(self):
        import os
        self.enabled = _plateau_enabled()
        self.cap = _plateau_assist_cap()
        # SHEEP_PLATEAU_FORCE=1: plateau mode from round one — the
        # detection boundaries stop mattering, so tests and dryrun arms
        # can certify the assist machinery on inputs too small to
        # plateau naturally
        self.on = self.enabled and \
            os.environ.get("SHEEP_PLATEAU_FORCE", "") == "1"
        self.prev_live: int | None = None
        self.assists = 0
        self.walks = 0
        self.bail: int | None = None  # stragglers at the last capped bail
        self.assisted = False  # a non-bailed assist attempt has run

    def observe(self, moved: int, live: int) -> None:
        if not self.enabled or self.on:
            self.prev_live = live
            return
        if self.prev_live is not None and live > self.RATIO * self.prev_live:
            self.on = True
        if moved > 0 and moved * self.MOVED_FRAC <= live:
            self.on = True
        self.prev_live = live

    def wants_assist(self, moved: int) -> bool:
        if not (self.enabled and self.on and 0 < moved <= self.cap):
            return False
        # after a capped bail, retry only once the mover count has
        # clearly decayed — straggler counts track movers, and even a
        # bailed attempt pays the full state fetch
        return self.bail is None or moved * 2 <= self.bail

    def assist(self, lo, hi, n_cur: int):
        """Run one host assist; returns (lo, advanced: bool) — advanced
        False means the walk bailed (capped) or found nothing, and the
        caller must not book a round for it."""
        l = np.asarray(lo).astype(np.int64)
        h = np.asarray(hi).astype(np.int64)
        f = np.asarray(min_up_table(lo, hi, n_cur)).astype(np.int64)
        l_orig = l.copy()
        walks, _, stragglers = plateau_assist_walk(l, h, f, n_cur,
                                                   cap=self.cap)
        if walks == 0 and stragglers > self.cap:
            self.bail = stragglers
            return lo, False
        self.bail = None
        self.assisted = True
        if not walks:
            return lo, False
        self.assists += 1
        self.walks += walks
        changed = np.nonzero(l != l_orig)[0]
        k = _pad_pow2_min(changed.size)
        idx = np.full(k, lo.shape[0], np.int32)
        vals = np.zeros(k, np.int32)
        idx[:changed.size] = changed
        vals[:changed.size] = l[changed]
        return _scatter_lo(lo, jnp.asarray(idx), jnp.asarray(vals), k), True


def _pipe_width_ok(width: int, pad: int) -> bool:
    """The pipelined-dispatch width gate: engage only at 4x-compacted
    AND width <= 2^17 — where one hidden host sync outweighs the
    one-chunk-late compaction's stale-width compute (PERF_NOTES round
    5 put the break-even near W ~ 1e5 at j=8 rounds)."""
    return 4 * width <= pad and width <= (1 << 17)


def _pipeline_chunks() -> bool:
    """Pipelined chunk dispatch gate (SHEEP_PIPELINE_CHUNKS overrides):
    default ON off-cpu, where each stats fetch is a device->host sync,
    and OFF on the cpu backend, where the stats fetch is instant and the
    one-chunk-late compaction would only cost width."""
    import os
    v = os.environ.get("SHEEP_PIPELINE_CHUNKS", "")
    if v != "":
        return v == "1"
    return jax.devices()[0].platform != "cpu"


#: per-chunk round counts — probe every round while live is collapsing
#: (rounds 1-3 kill 85-93% of edges, and an early stop at the knee saves
#: both compute and handoff transfer), then batch rounds once the arrays
#: are compact so the per-chunk host sync amortizes.  The fixed
#: tuple bounds the (shape, jrounds) axes of what XLA compiles; the
#: vertex remap adds an n_cur axis (one fresh fixpoint_chunk compile per
#: remap, <= log4(n/4096) per run, amortized by the persistent cache).
_CHUNK_SCHEDULE = (1, 1, 1, 2, 4)


def _depth_tier(size: int, pad: int, in_schedule: bool, levels: int,
                first_levels: int, cap: int) -> int:
    """Three-tier lifting depth shared by the hosted and mesh chunk loops
    (round-4 A/B, PERF_NOTES): light ``first_levels`` while the ARRAYS
    are still at full size (full-width gathers cost most and early
    progress is dedupe/star-collapse); ``levels+2`` mid-phase;
    ``levels+6`` once compaction is below an eighth of the original
    padded size (late-phase gathers are cheap and the remaining cost is
    chain DEPTH, which deep tables cut exponentially).

    ``size`` is the current ARRAY length — the gather width actually
    paid, which is what the tier trades against depth.  Tiering on the
    live count instead was A/B'd and lost (2^22: 109.7-114.6s vs 98.5s;
    deep tiers engaged a fetch earlier, on still-wide arrays).  Measured
    vs flat levels=10 on the pure-device path: 24.7->18.0s at 2^20,
    181.8->98.5s (1.85x) at 2^22, parents bit-identical; 14/18 tiers
    slightly worse.  Caveat: compaction floors at 4096 slots, so inputs
    with pad <= 16384 never reach the deep tier — at those sizes the
    whole build is milliseconds and depth is irrelevant.
    """
    if in_schedule and size >= pad:
        return first_levels
    if size > pad // 8:
        return min(levels + 2, cap)
    return min(levels + 6, cap)


def _live_links_np(lo, hi, n: int):
    """Host copies of the live links (lo < n) — the checkpointable state
    at a chunk boundary (runtime/snapshot.py's soundness argument)."""
    l = np.asarray(lo)
    h = np.asarray(hi)
    keep = l < n
    return l[keep], h[keep]


def reduce_links_hosted(lo, hi, n: int, stop_live: int = 0,
                        levels: int = 10, jrounds: int = 8,
                        first_levels: int = 4,
                        handoff_input: bool = False,
                        runtime=None):
    """Run chunk rounds until convergence (or until live <= stop_live),
    compacting between dispatches.

    lo/hi: int32 device or host arrays, sentinel n for dead slots.  Returns
    (lo, hi, live, rounds, converged) with lo/hi on device, all remaining
    live links in the first ``live`` slots' prefix region (plus possibly a
    few dead ones — callers must still mask lo < n).

    A sort-free jump-only opener round runs first, then chunks follow
    ``_CHUNK_SCHEDULE`` and repeat ``jrounds``; lifting depth escalates
    per :func:`_depth_tier` as the live set collapses (``levels`` is the
    mid-phase base: effective depth is levels+2 mid, levels+6 late,
    capped at log2(n)).  Once the arrays have compacted far enough
    (2 * cols <= n/4), the VERTEX space compacts too
    (:func:`vremap_compact`, SHEEP_VREMAP=0 disables): later rounds'
    O(n * levels) jump-table work becomes O(cols * levels), which on the
    measured backends is the whole cost of the late phase.  The returned
    links are always back in the original vertex space.

    Once the per-chunk stats show the live count has PLATEAUED, the
    round-6 adaptive scheduler takes over (:class:`_PlateauSched`,
    SHEEP_PLATEAU_ADAPT=0 disables): chunks shrink to j=1 at late-tier
    depth, the remap trigger relaxes, and the sequential straggler
    crawl that otherwise consumes most of the build's rounds (~80 of 90
    at 2^22) is resolved by bounded host assists
    (:func:`plateau_assist_walk`) — measured 90 -> 13 rounds at 2^22
    on the cpu backend, parents bit-identical.

    ``runtime`` — optional runtime.ChunkRuntime: wraps every dispatch in
    the retry/backoff/watchdog policy (halving the per-dispatch round
    count on a fault) and checkpoints the live links at each chunk
    boundary while the loop is still in the original vertex space (once a
    vertex remap engages, the last pre-remap checkpoint stands — the
    remap is an optimization detail a resume need not replay).  Fault
    tolerance trades the pipelined-dispatch overlap away: checkpoint
    boundaries need settled state, so the pipeline is disabled.
    """
    lo = jnp.asarray(lo, jnp.int32)
    hi = jnp.asarray(hi, jnp.int32)
    e = int(lo.shape[0])
    if e == 0:
        return lo, hi, 0, 0, True
    pad = _pad_pow2(e)
    if pad != e:
        fill = jnp.full(pad - e, n, jnp.int32)
        lo = jnp.concatenate([lo, fill])
        hi = jnp.concatenate([hi, fill])
    if handoff_input and stop_live and e <= stop_live:
        # The input already satisfies the handoff threshold AND the
        # caller promised the output goes straight to the native
        # union-find (``handoff_input`` — NOT the streaming folds, whose
        # carry contract needs the dedupe rounds): the opener + a sorted
        # chunk retire ~nothing before the live check stops the loop
        # anyway (measured 10.3s of a 13.8s CPU hybrid at 2^22 with
        # factor 8, where stop_live == E).  The links go back in raw
        # order: the cache-blocked kernel's quantile bucketing reads raw
        # order faster than a device sort costs (1.54s raw fold vs 3.65s
        # sort + 0.98s sorted fold at 2^22 on the 1-core host), and the
        # windowed tail sorts by hi itself where it streams windows.
        # The returned count stays the sentinel-inclusive upper bound;
        # callers' lo < n filter drops dead slots.
        return lo, hi, e, 0, False
    rounds = 0
    chunk_i = 0
    n_cur = n  # current vertex-space size (shrinks at each remap)
    back = None  # compact id -> ORIGINAL position, composed across remaps
    remap_on = _vremap_enabled()

    def _restore(lo, hi):
        return (lo, hi) if back is None else vremap_back(lo, hi, back)
    # Jump-only opener: on the full-size arrays the sort is the most
    # expensive op and round 1's sort retires almost nothing (~6%) — the
    # collisions this jump creates are what round 2's sort dedupes.  26%
    # faster to the hybrid handoff at 2^18 (scripts/sched_ab.py).  Its
    # stats are deliberately NOT fetched (each is a host sync, and the
    # streaming path calls this per block);
    # an already-converged input just costs one cheap sorted chunk below.
    if runtime is None:
        lo, hi, _ = jump_chunk(lo, hi, n, first_levels)
    else:
        (lo, hi, _), _ = runtime.dispatch(
            "chunk", lambda _j: jump_chunk(lo, hi, n, first_levels))
    rounds += 1
    # Pipelined dispatch (round 5, SHEEP_PIPELINE_CHUNKS; default ON
    # off-cpu): keep the NEXT chunk in flight while the previous chunk's
    # stats come back to the host, so per-chunk sync hides
    # behind device compute.  Sound one-chunk-late compaction: live
    # counts decrease monotonically across chunks and rewrites never
    # resurrect a dead slot, so every live link of chunk k+1's output
    # sits within the first pad(live_k) slots.  Costs: the in-flight
    # chunk runs at the pre-compaction width, and a stop/convergence is
    # detected one chunk late (that chunk's output is discarded and its
    # rounds uncounted).  Disabled once a vertex remap engages (the
    # remap needs exact state; the pipeline drains first).
    pipeline = _pipeline_chunks() and runtime is None
    prev = None  # (lo, hi, stats) of the chunk whose stats are unread

    def _consume(stats, alo, ahi, rounds_ret):
        """THE exit policy after a chunk's stats resolve, shared by the
        sync, pipelined, and drain sites so they cannot drift: returns
        (exit_tuple | None, live, moved).  A non-None exit_tuple is the
        loop's return value, arrays restored to the original vertex
        space."""
        moved_i, live_i = (int(x) for x in np.asarray(stats))  # one sync
        # flight recorder: one event per resolved chunk — the round-level
        # record `sheep trace` rolls up (round counts from ONE code path)
        _obs.event("reduce.chunk", live=live_i, moved=moved_i,
                   rounds=rounds_ret)
        if moved_i == 0:
            rlo, rhi = _restore(alo, ahi)
            return (rlo, rhi, live_i, rounds_ret, True), live_i, moved_i
        if stop_live and live_i <= stop_live:
            rlo, rhi = _restore(alo, ahi)
            return (rlo, rhi, live_i, rounds_ret, False), live_i, moved_i
        return None, live_i, moved_i

    def _compact(alo, ahi, live_i):
        target = _pad_pow2(live_i)
        if target <= alo.shape[0] // 2:
            return alo[:target], ahi[:target]
        return alo, ahi

    plate = _PlateauSched()
    while True:
        j = _CHUNK_SCHEDULE[chunk_i] if chunk_i < len(_CHUNK_SCHEDULE) \
            else jrounds
        cap = int(np.ceil(np.log2(n_cur + 2)))
        lv = _depth_tier(int(lo.shape[0]), pad,
                         chunk_i < len(_CHUNK_SCHEDULE),
                         levels, first_levels, cap)
        if plate.on:
            # plateau: late-tier depth so any straggler whose chain IS
            # materialized crosses it in one round; once an assist has
            # run, j=1 chunks so the exit check lands the moment its
            # cascade resolves (a j=8 chunk would book 8 rounds for a
            # convergence that happened in its first)
            lv = min(levels + 6, cap)
            if plate.assisted:
                j = 1
        if runtime is not None:
            # memory budget (ISSUE 5): the jump tables are the loop's
            # dominant O(n) allocation — cap the depth to the headroom
            lv = runtime.cap_levels(lv, n_cur)
        if runtime is None:
            nlo, nhi, stats = fixpoint_chunk(lo, hi, n_cur, lv, j)
        else:
            # the retry wrapper may shrink j (a dispatch that faulted asks
            # for half the rounds next attempt); account the shrunk value
            (nlo, nhi, stats), j = runtime.dispatch(
                "chunk", lambda jj: fixpoint_chunk(lo, hi, n_cur, lv, jj), j)
        rounds += j
        chunk_i += 1
        # width gate: pipeline only once the arrays are small.  Early
        # full-width chunks carry most of the compute, and the
        # one-chunk-late compaction makes them run at stale widths — a
        # forced-pipeline A/B on the instant-stats cpu backend measured
        # +29.5% end-to-end ungated and +16.7% gated at 4x-compacted
        # (PERF_NOTES round 5).  The hidden sync saves one host round
        # trip per chunk, which only outweighs the stale-width compute
        # on small arrays — hence the absolute cap alongside the
        # relative one.  Width is
        # monotone non-increasing, so the mode never flips back —
        # except onto the plateau, whose host assists need settled
        # state between every chunk (drained below).
        use_pipe = pipeline and back is None and not plate.on \
            and _pipe_width_ok(int(lo.shape[0]), pad)
        if not use_pipe:
            if prev is not None:
                # the gate just turned off (plateau flip): drain the
                # in-flight chunk's predecessor stats first (prev's
                # arrays are this dispatch's inputs, lo/hi)
                _, _, pstats = prev
                prev = None
                exit_t, live_i, _ = _consume(pstats, lo, hi, rounds - j)
                if exit_t is not None:
                    return exit_t
                nlo, nhi = _compact(nlo, nhi, live_i)
            exit_t, live_i, moved_i = _consume(stats, nlo, nhi, rounds)
            if exit_t is not None:
                return exit_t
            lo, hi = _compact(nlo, nhi, live_i)
            plate.observe(moved_i, live_i)
            if plate.wants_assist(moved_i):
                # host straggler walk (one round's worth of the same
                # transform, executed sparsely where sequential work is
                # cheap); counted as a round — see _PlateauSched
                lo, advanced = plate.assist(lo, hi, n_cur)
                if advanced:
                    rounds += 1
            if runtime is not None and back is None:
                # chunk boundary: persist the live multiset (original
                # vertex space only — the snapshot soundness contract)
                runtime.boundary(
                    rounds, lambda: _live_links_np(lo, hi, n))
        else:
            if prev is not None:
                plo, phi, pstats = prev
                # resolves while the chunk dispatched above runs; on an
                # exit the in-flight chunk is discarded, its rounds
                # uncounted (rounds - j)
                exit_t, live_i, moved_i = _consume(pstats, plo, phi,
                                                   rounds - j)
                if exit_t is not None:
                    return exit_t
                # one-chunk-late compaction of the IN-FLIGHT output
                nlo, nhi = _compact(nlo, nhi, live_i)
                # a plateau observed here un-gates the pipeline next
                # iteration; the drain above settles state for assists
                plate.observe(moved_i, live_i)
            prev = (nlo, nhi, stats)
            lo, hi = nlo, nhi
        cols = int(lo.shape[0])
        # remap trigger: >= 4x table-work shrink normally; on the
        # plateau a 2x shrink already pays (many deep rounds may remain
        # when the assist is capped out, and the dense space halves
        # every table squaring)
        remap_den = 2 if plate.on else 4
        if remap_on and 2 * cols <= n_cur // remap_den \
                and n_cur > (1 << 16):
            if prev is not None:
                # drain the pipeline: the remap needs exact, settled
                # state (prev's arrays ARE lo/hi here)
                _, _, pstats = prev
                prev = None
                exit_t, live_i, _ = _consume(pstats, lo, hi, rounds)
                if exit_t is not None:
                    return exit_t
                lo, hi = _compact(lo, hi, live_i)
                # _compact only ever shrinks, so the remap trigger
                # (checked on the pre-drain width) still holds here
                cols = int(lo.shape[0])
            # each remap shrinks table work; the O(n_cur) forward
            # table build amortizes over every remaining round
            lo, hi, back_step = vremap_compact(lo, hi, n_cur, 2 * cols)
            back = back_step if back is None else back[back_step]
            n_cur = 2 * cols
    # unreachable


def forest_fixpoint_hosted(lo, hi, n: int, levels: int = 10,
                           jrounds: int = 8):
    """Host-orchestrated fixpoint: the production equivalent of
    :func:`forest_fixpoint` for real hardware.  Returns (parent int32
    device array [n] with n marking roots, rounds)."""
    lo, hi, live, rounds, _ = reduce_links_hosted(
        lo, hi, n, levels=levels, jrounds=jrounds)
    return parent_from_links(lo, hi, n), rounds


@functools.partial(jax.jit, static_argnames=("n",))
def pst_weights(lo: jnp.ndarray, n: int) -> jnp.ndarray:
    """Per-node postorder edge weight: one count per live link at its lo
    (jtree.cpp:47-49 equivalent; slot n absorbs sentinel links)."""
    return jnp.zeros(n + 1, jnp.int32).at[lo.astype(jnp.int32)].add(1)[:n]


def links_from_parent(parent: jnp.ndarray, n: int):
    """A forest's (kid -> parent) pairs as sentinel-padded link arrays."""
    kid = jnp.arange(n, dtype=jnp.int32)
    live = parent < n
    lo = jnp.where(live, kid, jnp.int32(n))
    hi = jnp.where(live, parent.astype(jnp.int32), jnp.int32(n))
    return lo, hi


@functools.partial(jax.jit, static_argnames=("n",))
def merge_parents(parents: jnp.ndarray, psts: jnp.ndarray, n: int):
    """Merge k same-sequence partial forests (lib/jnode.cpp:174-250).

    parents: int32 [k, n] with n marking roots; psts: int32 [k, n].
    Returns (parent int32 [n], pst int32 [n], rounds).
    """
    k = parents.shape[0]
    kid = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (k, n))
    live = parents < n
    lo = jnp.where(live, kid, jnp.int32(n)).reshape(-1)
    hi = jnp.where(live, parents.astype(jnp.int32), jnp.int32(n)).reshape(-1)
    parent, rounds = forest_fixpoint(lo, hi, n)
    return parent, psts.sum(axis=0).astype(jnp.int32), rounds


# ---------------------------------------------------------------------------
# Host-facing wrappers (numpy in / Forest out), used by tests and the CLI.
# ---------------------------------------------------------------------------

def _to_forest(parent_dev: jnp.ndarray, pst_dev: jnp.ndarray, n: int) -> Forest:
    parent = np.asarray(parent_dev).astype(np.int64)
    pst = np.asarray(pst_dev).astype(np.uint32)
    out = np.full(n, INVALID_JNID, dtype=np.uint32)
    live = parent < n
    out[live] = parent[live].astype(np.uint32)
    return Forest(out, pst)


def build_forest_device(tail: np.ndarray, head: np.ndarray,
                        seq: np.ndarray, max_vid: int | None = None) -> Forest:
    """Device-built Forest from raw edge records (test/CLI entry point)."""
    from ..core.forest import edges_to_positions

    lo, hi = edges_to_positions(tail, head, seq, max_vid)
    n = len(seq)
    # pst-only links (hi = INVALID: edge to a vertex absent from the
    # sequence) count toward pst but must be sentineled out of the fixpoint.
    pst_d = pst_weights(jnp.asarray(lo, dtype=jnp.int32), n)
    pst_only = hi >= n
    lo_d = jnp.asarray(np.where(pst_only, n, lo), dtype=jnp.int32)
    hi_d = jnp.asarray(np.where(pst_only, n, hi), dtype=jnp.int32)
    parent, _ = forest_fixpoint(lo_d, hi_d, n)
    return _to_forest(parent, pst_d, n)


def merge_forests_device(*forests: Forest) -> Forest:
    """Device merge of host Forests (equivalent to core.merge_forests)."""
    n = forests[0].n
    parents = np.stack([
        np.where(f.parent == INVALID_JNID, n, f.parent.astype(np.int64))
        for f in forests]).astype(np.int32)
    psts = np.stack([f.pst_weight.astype(np.int32) for f in forests])
    parent, pst, _ = merge_parents(jnp.asarray(parents), jnp.asarray(psts), n)
    return _to_forest(parent, pst, n)
