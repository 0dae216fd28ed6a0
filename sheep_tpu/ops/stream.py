"""Out-of-core streaming build: edge blocks from host DRAM into the device.

The reference's OOM story is partial loads with more partials than cores
(scripts/horizontal-dist.sh:22-24, README:112-122): workers stream
edge-disjoint slices and the associative tree merge stitches them.  The
device analog keeps only O(n + B) state resident: a carry forest (two
length-n arrays) plus one B-edge block.  Each block step rebuilds the forest
from (carry links + block links) with the fixpoint kernel — correct because
a forest re-enters as its own link set and the merge is associative
(lib/jnode.cpp:174-201).  pst accumulates as a segment-sum per block.

Shapes are static (one compilation for any number of blocks), and JAX's
async dispatch overlaps the host memmap read of block k+1 with the device
compute of block k — the double-buffering the reference gets from OS
readahead.
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from .. import INVALID_JNID
from ..core.forest import Forest
from .forest import forest_fixpoint, pst_weights
from .sort import degree_histogram


@functools.partial(jax.jit, static_argnames=("n",))
def stream_block_step(parent: jnp.ndarray, pst: jnp.ndarray,
                      tail: jnp.ndarray, head: jnp.ndarray,
                      pos: jnp.ndarray, n: int):
    """Fold one edge block into the carry forest.

    parent int32 [n] (n = root sentinel), pst int32 [n], tail/head int32 [B]
    (pad with values >= V), pos int32 [V+1] over the FULL vid space (V =
    max vid + 1, which can far exceed the n active positions — zero-degree
    vids exist between active ones), absent vids and the pad slot mapped
    to n.
    """
    sent = jnp.int32(n)
    vid_cap = jnp.int32(pos.shape[0] - 1)
    pt = pos[jnp.minimum(tail, vid_cap)]
    ph = pos[jnp.minimum(head, vid_cap)]
    lo = jnp.minimum(pt, ph)
    hi = jnp.maximum(pt, ph)
    # pst: every block edge with a present earlier endpoint, absent-endpoint
    # edges included (pst-only contract); loops/padding (lo == hi) excluded.
    pst = pst + pst_weights(jnp.where(lo == hi, sent, lo), n)
    dead = (lo >= hi) | (hi >= sent)
    blo = jnp.where(dead, sent, lo)
    bhi = jnp.where(dead, sent, hi)
    # carry forest re-enters as its own links
    kid = jnp.arange(n, dtype=jnp.int32)
    clive = parent < sent
    clo = jnp.where(clive, kid, sent)
    chi = jnp.where(clive, parent, sent)
    mlo = jnp.concatenate([clo, blo])
    mhi = jnp.concatenate([chi, bhi])
    new_parent, rounds = forest_fixpoint(mlo, mhi, n)
    return new_parent, pst, rounds


def build_graph_streaming(blocks, n: int, pos: np.ndarray,
                          block_edges: int):
    """Fold an iterator of (tail, head) uint32 blocks into a Forest.

    ``pos``: vid -> position table over the FULL vid space (length >= max
    vid + 1; INVALID for absent vids).  Returns (Forest over n positions,
    total_rounds).  Memory: O(n + V + block_edges) device-resident.
    """
    sent = np.int32(n)
    pos_d = jnp.asarray(_full_vid_pos(pos, n))
    vid_pad = len(pos)  # pad records map to the table's sentinel slot

    parent = jnp.full(n, sent, jnp.int32)
    pst = jnp.zeros(n, jnp.int32)
    round_counts = []  # device arrays; summing later keeps dispatch async
    for tail, head in blocks:
        b = len(tail)
        t = np.full(block_edges, vid_pad, dtype=np.int64)
        h = np.full(block_edges, vid_pad, dtype=np.int64)
        t[:b] = tail
        h[:b] = head
        parent, pst, rounds = stream_block_step(
            parent, pst, jnp.asarray(t, jnp.int32), jnp.asarray(h, jnp.int32),
            pos_d, n)
        round_counts.append(rounds)
    total_rounds = int(sum(int(r) for r in round_counts)) if round_counts else 0
    parent_np = np.asarray(parent).astype(np.int64)
    out = np.full(n, INVALID_JNID, dtype=np.uint32)
    live = parent_np < n
    out[live] = parent_np[live].astype(np.uint32)
    return Forest(out, np.asarray(pst).astype(np.uint32)), total_rounds


def _full_vid_pos(pos: np.ndarray, n: int) -> np.ndarray:
    """Sanitize a vid->position table for device use: full vid space plus
    one trailing sentinel slot; absent/invalid entries map to n."""
    posx = np.full(len(pos) + 1, n, dtype=np.int32)
    p = pos.astype(np.int64)
    posx[:-1] = np.where((p < 0) | (p >= n), n, p).astype(np.int32)
    return posx


@functools.partial(jax.jit, static_argnames=("n",))
def _block_links(tail, head, pos, n: int):
    """Map one padded edge block to (lo, hi, pst_block) in one dispatch.

    ``pos``: the _full_vid_pos table ([V+1], sentinel slot last)."""
    sent = jnp.int32(n)
    vid_cap = jnp.int32(pos.shape[0] - 1)
    pt = pos[jnp.minimum(tail, vid_cap)]
    ph = pos[jnp.minimum(head, vid_cap)]
    lo = jnp.minimum(pt, ph)
    hi = jnp.maximum(pt, ph)
    pst = pst_weights(jnp.where(lo == hi, sent, lo), n)
    dead = (lo >= hi) | (hi >= sent)
    return jnp.where(dead, sent, lo), jnp.where(dead, sent, hi), pst


def build_graph_streaming_hosted(blocks, n: int, pos: np.ndarray,
                                 block_edges: int):
    """Production OOM streaming build: hosted chunked reduction per block.

    Same contract as :func:`build_graph_streaming` but the per-block fold
    uses the host-orchestrated reducer (ops.forest.reduce_links_hosted):
    bounded per-dispatch execution time (no device faults at scale) and
    carry compaction between blocks — the carry is the live link set, at
    most ~n entries once reduction converges, concatenated with each new
    block's links.  Returns (Forest over n positions, total_rounds).
    """
    from .forest import parent_from_links, reduce_links_hosted

    pos_d = jnp.asarray(_full_vid_pos(pos, n))
    vid_pad = len(pos)

    from .forest import _pad_pow2

    carry_lo = carry_hi = None
    pst = jnp.zeros(n, jnp.int32)
    total_rounds = 0
    for tail, head in blocks:
        b = len(tail)
        t = np.full(block_edges, vid_pad, dtype=np.int64)
        h = np.full(block_edges, vid_pad, dtype=np.int64)
        t[:b] = tail
        h[:b] = head
        lo, hi, pst_b = _block_links(
            jnp.asarray(t, jnp.int32), jnp.asarray(h, jnp.int32), pos_d, n)
        pst = pst + pst_b
        if carry_lo is not None:
            lo = jnp.concatenate([carry_lo, lo])
            hi = jnp.concatenate([carry_hi, hi])
        # Mid-stream the carry only needs to stay BOUNDED (a few rounds
        # kill the duplicate/star bulk); full convergence happens once,
        # after the last block — ~3-5 rounds per block instead of ~30.
        lo, hi, live, rounds, _ = reduce_links_hosted(
            lo, hi, n, stop_live=2 * n)
        total_rounds += rounds
        target = _pad_pow2(live)
        carry_lo, carry_hi = lo[:target], hi[:target]
    if carry_lo is None:
        return Forest(np.full(n, INVALID_JNID, np.uint32),
                      np.zeros(n, np.uint32)), 0
    # Final fold ends like the hybrid: reduce to the platform-tuned
    # handoff threshold and let the native union-find chase the residue —
    # the device-convergence tail was measured at hundreds of rounds on
    # the last few thousand links (SCALE_r03: 781 total rounds).
    from .build import (default_handoff_factor, handoff_input_ok,
                        reduce_and_finish_native)
    # same production reduce+tail as the hybrid.  pst here is the
    # accumulated per-block count, NOT recoverable from the carry links
    # (they were rewritten by the mid-stream folds), so the fold always
    # receives it precomputed.
    pst_np = np.asarray(pst).astype(np.uint32)
    res = reduce_and_finish_native(
        carry_lo, carry_hi, n, stop_live=default_handoff_factor() * n,
        handoff_input=handoff_input_ok(), pst_h=pst_np)
    total_rounds += res[4]
    if res[0] == "device":  # converged before the handoff threshold
        parent = parent_from_links(res[1], res[2], n)
        parent_np = np.asarray(parent).astype(np.int64)
        out = np.full(n, INVALID_JNID, dtype=np.uint32)
        live_mask = parent_np < n
        out[live_mask] = parent_np[live_mask].astype(np.uint32)
        return Forest(out, pst_np), total_rounds
    _, parent_h, pst_out, _, _ = res
    return Forest(parent_h.copy(), pst_out.copy()), total_rounds


def streaming_degree_histogram(blocks, n: int) -> np.ndarray:
    """Degree histogram from an edge-block iterator (device bincount)."""
    deg = jnp.zeros(n, jnp.int32)
    for tail, head in blocks:
        deg = deg + degree_histogram(jnp.asarray(tail, jnp.int32),
                                     jnp.asarray(head, jnp.int32), n)
    return np.asarray(deg).astype(np.int64)
