"""Partition quality evaluator.

Computes exactly the metrics of the reference's exhaustive evaluator
(lib/partition.cpp:428-521), but with per-vertex part-set bitmaps (the C
kernel, one pass over the edges) or sorts of (vertex, part) keys (the
numpy oracle) instead of per-vertex hash-set scans ("evaluation is
exhaustive, not efficient", reference README:105 — here it is both):

  edges cut   undirected edges whose endpoints differ in part
  Vcom. vol   communication volume: per vertex, distinct neighbor parts
              beyond its own
  ECV(hash)   edge communication volume when each edge lives on the part of
              its hash-min endpoint (cormen_hash, partition.cpp:423-427)
  ECV(down)   edge CV under *downward* assignment — edge lives with its
              earlier-in-sequence endpoint (the paper's objective)
  ECV(up)     the reverse
  balances    max part load for each notion of load

Percentages follow the reference's printf quirk: the printed "(x%)" value is
the raw fraction of |E| (or of E/np, N/np for balances), not multiplied by
100 (partition.cpp:468-472,517-520).

The denominator |E| is the number of file records, matching LLAMA's
``getEdges()`` which includes self-loops ("XXX" note at partition.cpp:467).
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from ..obs import trace as obs
from ..resources.governor import native_thread_plan, native_threads_env

_CORMEN_MULT = np.uint64(2654435769)  # floor(0.5*(sqrt(5)-1) * 2^32)


def cormen_hash(k: np.ndarray) -> np.ndarray:
    return (k.astype(np.uint64) * _CORMEN_MULT & np.uint64(0xFFFFFFFF)).astype(np.uint32)


def _nunique_pairs(x: np.ndarray, y: np.ndarray, y_card: int) -> int:
    key = x.astype(np.int64) * np.int64(y_card) + y.astype(np.int64)
    return len(np.unique(key))


@dataclass
class EvalReport:
    edges_cut: int
    vcom_vol: int
    ecv_hash: int
    ecv_down: int
    ecv_up: int
    vertex_balance: int
    hash_balance: int
    down_balance: int
    up_balance: int
    num_edges: int
    num_nodes: int
    num_parts: int

    def print(self, with_seq: bool = True) -> None:
        e = self.num_edges
        n = self.num_nodes
        np_ = max(self.num_parts, 1)
        # Balance denominators use truncating integer division like the
        # reference's size_t arithmetic (partition.cpp:470-472,518-520);
        # division by a zero denominator prints inf like C double division.
        div = lambda v, d: (v / d) if d else float("inf")
        print(f"edges cut: {self.edges_cut} ({div(self.edges_cut, e):f}%)")
        print(f"Vcom. vol: {self.vcom_vol} ({div(self.vcom_vol, e):f}%)")
        print(f"  balance: {self.vertex_balance} ({div(self.vertex_balance, n // np_):f}%)")
        print(f"ECV(hash): {self.ecv_hash} ({div(self.ecv_hash, e):f}%)")
        print(f"  balance: {self.hash_balance} ({div(self.hash_balance, e // np_):f}%)")
        if with_seq:
            print(f"ECV(down): {self.ecv_down} ({div(self.ecv_down, e):f}%)")
            print(f"  balance: {self.down_balance} ({div(self.down_balance, e // np_):f}%)")
            print(f"ECV(up)  : {self.ecv_up} ({div(self.ecv_up, e):f}%)")
            print(f"  balance: {self.up_balance} ({div(self.up_balance, e // np_):f}%)")


def evaluate_partition(parts: np.ndarray, tail: np.ndarray, head: np.ndarray,
                       seq: np.ndarray | None, num_parts: int,
                       max_vid: int | None = None,
                       file_edges: int | None = None,
                       impl: str = "auto") -> EvalReport:
    """``seq=None`` evaluates the sequence-free metrics only (the
    reference's evaluate(graph) overload, partition.cpp:428-473); the
    ECV(down)/(up) fields then come back zero — print with
    ``with_seq=False``.

    ``impl``: auto|native|python.  With the native runtime the distinct
    (vertex, part) counts are part-set bitmaps that the C kernel
    (sheep_eval_block) fills in one pass over the loaded arrays; "python",
    or a missing runtime under "auto", runs the sort body, the oracle.
    The two give equal reports."""
    from ..core.forest import native_or_none
    native = native_or_none(impl)
    # one span, with a child for each of the body's steps
    with obs.span("partition.evaluate", records=len(tail),
                  keys=2 * len(tail), parts=num_parts, streamed=False,
                  kernel="sort" if native is None else "bitmap"):
        if native is None:
            return _evaluate(parts, tail, head, seq, num_parts, max_vid,
                             file_edges)
        return _evaluate_bitmap(parts, tail, head, seq, num_parts, max_vid,
                                file_edges, native)


def _part_count(parts: np.ndarray) -> int:
    return max(int(parts.max(initial=0)) + 1, 1)


def _pass_words(n: int, e: int, P: int, maps: int) -> tuple[int, int]:
    """(words, passes) of the in-memory bitmap scan.  A pass covers
    ``words`` 64-part windows; its ``maps`` bitmaps of n x words uint64
    stay within the bytes of the sort body's doubled int64 key array
    (16 a record), at least one window a pass, and the passes split the
    windows evenly."""
    windows = -(-P // 64)
    fit = max(1, 16 * e // (8 * maps * max(n, 1)))
    passes = -(-windows // fit)
    return -(-windows // passes), passes


def _evaluate_bitmap(parts, tail, head, seq, num_parts, max_vid, file_edges,
                     native):
    from ..core.sequence import sequence_positions

    parts = np.ascontiguousarray(parts, dtype=np.int64)
    tail = np.ascontiguousarray(tail, dtype=np.uint32)
    head = np.ascontiguousarray(head, dtype=np.uint32)
    pos = None if seq is None else sequence_positions(seq, max_vid)
    P = _part_count(parts)
    words, passes = _pass_words(len(parts), len(tail), P,
                                2 if pos is None else 4)
    E = file_edges if file_edges is not None else len(tail)
    return _bitmap_eval(parts, P, lambda: ((tail, head),), pos, num_parts,
                        E, native, words, passes=passes, source="memory")


def _evaluate(parts, tail, head, seq, num_parts, max_vid, file_edges):
    from ..core.sequence import sequence_positions

    with obs.span("evaluate.gather"):
        parts = parts.astype(np.int64)
        t = tail.astype(np.int64)
        h = head.astype(np.int64)
        E = file_edges if file_edges is not None else len(t)
        pos = None
        if seq is not None:
            pos = sequence_positions(seq, max_vid).astype(np.int64)

        deg_mask = np.zeros(len(parts), dtype=bool)
        deg_mask[t] = True
        deg_mask[h] = True
        n_active = int(deg_mask.sum())
        P = _part_count(parts)

        pt, ph = parts[t], parts[h]

        # edges cut: once per record, self-loops never differ
        edges_cut = int((pt != ph).sum())

        # directed-doubled views
        X = np.concatenate([t, h])
        Y = np.concatenate([h, t])
        pX = np.concatenate([pt, ph])
        pY = np.concatenate([ph, pt])

    with obs.span("evaluate.vcom"):
        # Vcom_vol: distinct (X, part[Y]) pairs, seeded with (X, part[X])
        active = np.nonzero(deg_mask)[0]
        vx = np.concatenate([X, active])
        vy = np.concatenate([pY, parts[active]])
        vcom = _nunique_pairs(vx, vy, P) - n_active

    with obs.span("evaluate.ecv_hash"):
        # ECV(hash): per directed edge, part of the hash-smaller endpoint
        hX = cormen_hash(X.astype(np.uint32)).astype(np.int64)
        hY = cormen_hash(Y.astype(np.uint32)).astype(np.int64)
        hash_part = np.where(hX < hY, pX, pY)
        ecv_hash = _nunique_pairs(X, hash_part, P) - n_active
        # hash balance: once per undirected edge (the directed X<Y
        # filter), self-loops skipped; record orientation must not matter
        und = t != h
        a = np.minimum(t[und], h[und])
        b = np.maximum(t[und], h[und])
        ha = cormen_hash(a.astype(np.uint32)).astype(np.int64)
        hb = cormen_hash(b.astype(np.uint32)).astype(np.int64)
        und_hash_part = np.where(ha < hb, parts[a], parts[b])
        hash_balance = int(np.bincount(und_hash_part,
                                       minlength=P).max(initial=0))

    with obs.span("evaluate.ecv_seq"):
        # ECV(down)/(up): part of the earlier/later-in-sequence endpoint
        ecv_down = ecv_up = down_balance = up_balance = 0
        if pos is not None:
            posX = pos[X]
            posY = pos[Y]
            down_part = np.where(posX < posY, pX, pY)
            up_part = np.where(posX > posY, pX, pY)
            ecv_down = _nunique_pairs(X, down_part, P) - n_active
            ecv_up = _nunique_pairs(X, up_part, P) - n_active
            down_balance = int(np.bincount(pX[posX < posY],
                                           minlength=P).max(initial=0))
            up_balance = int(np.bincount(pX[posX > posY],
                                         minlength=P).max(initial=0))

        vertex_balance = int(np.bincount(parts[active],
                                         minlength=P).max(initial=0))

    return EvalReport(
        edges_cut=edges_cut,
        vcom_vol=vcom,
        ecv_hash=ecv_hash,
        ecv_down=ecv_down,
        ecv_up=ecv_up,
        vertex_balance=vertex_balance,
        hash_balance=hash_balance,
        down_balance=down_balance,
        up_balance=up_balance,
        num_edges=E,
        num_nodes=n_active,
        num_parts=num_parts,
    )


def evaluate_partition_streamed(parts: np.ndarray, blocks_factory,
                                pos: np.ndarray | None, num_parts: int,
                                file_edges: int,
                                impl: str = "auto") -> EvalReport:
    """Exact evaluator in O(n) memory for graphs whose doubled key arrays
    would not fit in host RAM (the in-memory path peaks at ~50 GB for
    twitter-2010; reference anchor lib/partition.cpp:428-521).

    The distinct-(vertex, part) counts behind Vcom/ECV are computed with
    per-vertex part-set *bitmaps*, as in :func:`evaluate_partition`'s
    native path: one uint64 per vertex covers a window of 64 parts, edges
    stream through in blocks, and windows repeat for num_parts > 64 —
    ceil(P/64) passes over the edge stream, each O(n) memory.  Results
    are bit-identical to :func:`evaluate_partition`.

    ``blocks_factory``: zero-arg callable returning a fresh iterator of
    (tail, head) uint32 blocks (e.g. ``lambda: iter_dat_blocks(path, B)``).
    ``pos``: vid -> sequence position table, or None for the sequence-free
    overload.  ``parts`` must cover every vid in the stream.  ``impl``:
    auto|native|python — the per-block work runs in the C runtime when
    available (sheep_eval_block, ~4x at 1.476B edges), with the numpy
    body as the oracle/fallback.
    """
    # one span, with two children: the block scan (every window's pass
    # over the stream) and the final balance count
    with obs.span("partition.evaluate", records=file_edges,
                  keys=2 * file_edges, parts=num_parts, streamed=True):
        return _evaluate_streamed(parts, blocks_factory, pos, num_parts,
                                  file_edges, impl)


def _evaluate_streamed(parts, blocks_factory, pos, num_parts, file_edges,
                       impl):
    from ..core.forest import native_or_none

    parts = np.ascontiguousarray(parts, dtype=np.int64)
    P = _part_count(parts)
    if pos is not None:
        pos = np.ascontiguousarray(pos, dtype=np.uint32)
    # one 64-part window a pass: the O(n) memory contract
    return _bitmap_eval(parts, P, blocks_factory, pos, num_parts, file_edges,
                        native_or_none(impl), 1)


def _bitmap_eval(parts, P, blocks_factory, pos, num_parts, file_edges,
                 native, words, **scan_attrs):
    """The part-set bitmap body of both evaluators.  A pass covers the
    parts [w0, w0 + 64*words): every block of ``blocks_factory()`` ORs one
    bit per (vertex, part) into the bitmaps (``native.eval_block``, or
    :func:`_eval_block_python` without the runtime); the pass then seeds
    Vcom with each active vertex's own part and popcounts.  The first pass
    also counts the cut, the active vertices and the balances' loads.
    ``parts`` int64, ``pos`` uint32 or None."""
    n = len(parts)
    width = 64 * words
    bitmaps = [np.zeros(n * words, dtype=np.uint64)
               for _ in range(2 if pos is None else 4)]
    m_vcom, m_hash, m_down, m_up = bitmaps + [None] * (4 - len(bitmaps))
    deg_mask = np.zeros(n, dtype=np.uint8)
    hash_loads, down_loads, up_loads = (np.zeros(P, dtype=np.int64)
                                        for _ in range(3))
    block = _eval_block_python if native is None else native.eval_block
    edges_cut = 0
    # distinct (vertex, part) pairs of Vcom, ECV(hash), ECV(down), ECV(up)
    pairs = [0] * len(bitmaps)

    # the scan's blocks run on the build's thread plan; the span's
    # ``threads`` is what the kernel's last call really used.  The plan
    # prices the build's 8n-byte partial tables a thread, which this scan
    # never allocates (its own is 24P bytes a thread), so a tight memory
    # budget can cut the scan's threads further than the scan needs
    with obs.span("evaluate.scan", windows=-(-P // 64), **scan_attrs) as sp, \
            native_threads_env(native_thread_plan(n)):
        for w0 in range(0, P, width):
            if w0:
                for m in bitmaps:
                    m.fill(0)
            for tail, head in blocks_factory():
                edges_cut += block(tail, head, parts, pos, w0, words, w0 == 0,
                                   m_vcom, m_hash, m_down, m_up, deg_mask,
                                   hash_loads, down_loads, up_loads, P)
            # Seed Vcom with each active vertex's own part (within this
            # pass); one bit a vertex, so no index repeats.
            active = np.nonzero(deg_mask)[0]
            d = parts[active] - w0
            sel = (d >= 0) & (d < width)
            d = d[sel]
            m_vcom[active[sel] * words + d // 64] |= \
                np.uint64(1) << (d % 64).astype(np.uint64)
            for i, m in enumerate(bitmaps):
                pairs[i] += int(np.bitwise_count(m).sum())
        if native is not None:
            native._annotate_threads(sp)

    with obs.span("evaluate.finish"):
        active = np.nonzero(deg_mask)[0]
        n_active = len(active)
        part_loads = np.bincount(parts[active], minlength=P)

    seqd = pos is not None
    return EvalReport(
        edges_cut=edges_cut,
        vcom_vol=pairs[0] - n_active,
        ecv_hash=pairs[1] - n_active,
        ecv_down=pairs[2] - n_active if seqd else 0,
        ecv_up=pairs[3] - n_active if seqd else 0,
        vertex_balance=int(part_loads.max(initial=0)),
        hash_balance=int(hash_loads.max(initial=0)),
        down_balance=int(down_loads.max(initial=0)),
        up_balance=int(up_loads.max(initial=0)),
        num_edges=file_edges,
        num_nodes=n_active,
        num_parts=num_parts,
    )


def _eval_block_python(tail, head, parts, pos, w0, words, first_window,
                       m_vcom, m_hash, m_down, m_up, deg_mask, hash_loads,
                       down_loads, up_loads, P):
    """The numpy body of ``sheep_eval_block``, the oracle of the native
    block: same arguments, same in-place updates, the cut's increment
    returned (np.bitwise_or.at is unbuffered, and ~40x slower)."""
    width = 64 * words

    def scatter_bits(mask, X, p):
        d = p - w0
        sel = (d >= 0) & (d < width)
        d = d[sel]
        np.bitwise_or.at(mask, X[sel] * words + d // 64,
                         np.uint64(1) << (d % 64).astype(np.uint64))

    t = tail.astype(np.int64)
    h = head.astype(np.int64)
    pt, ph = parts[t], parts[h]
    edges_cut = 0
    if first_window:
        deg_mask[t] = 1
        deg_mask[h] = 1
        edges_cut = int((pt != ph).sum())

    for X, Y, pX, pY in ((t, h, pt, ph), (h, t, ph, pt)):
        scatter_bits(m_vcom, X, pY)
        hX = cormen_hash(X.astype(np.uint32)).astype(np.int64)
        hY = cormen_hash(Y.astype(np.uint32)).astype(np.int64)
        scatter_bits(m_hash, X, np.where(hX < hY, pX, pY))
        if pos is not None:
            posX, posY = pos[X], pos[Y]
            scatter_bits(m_down, X, np.where(posX < posY, pX, pY))
            scatter_bits(m_up, X, np.where(posX > posY, pX, pY))

    if first_window:
        und = t != h
        a = np.minimum(t[und], h[und])
        b = np.maximum(t[und], h[und])
        ha = cormen_hash(a.astype(np.uint32)).astype(np.int64)
        hb = cormen_hash(b.astype(np.uint32)).astype(np.int64)
        hash_loads += np.bincount(np.where(ha < hb, parts[a], parts[b]),
                                  minlength=P)
        if pos is not None:
            post, posh = pos[t], pos[h]
            down_loads += np.bincount(pt[post < posh], minlength=P)
            up_loads += np.bincount(pt[post > posh], minlength=P)
            down_loads += np.bincount(ph[posh < post], minlength=P)
            up_loads += np.bincount(ph[posh > post], minlength=P)
    return edges_cut
