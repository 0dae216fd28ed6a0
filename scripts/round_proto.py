"""Round-count prototype for fixpoint variants (host numpy, exact).

Compares per-variant round counts and live-edge decay on the bench R-MAT
graphs, to choose the device kernel's round structure:

  jump[L]    current kernel: L-level binary-lifted jump (+ sort at rounds
             7,15,31,... like ops/forest.py)
  sort       pure sort rounds: star->chain rewrite + dedupe only
  sort+j[L]  sort round followed by an L-level jump using the post-sort f

Outputs one JSON line per (variant, log_n): rounds, live-edge counts after
rounds 1,2,4,8,..., and parent-array equality vs the oracle.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from sheep_tpu.utils import rmat_edges


def links_of(log_n):
    from sheep_tpu.core.sequence import degree_sequence, sequence_positions
    tail, head = rmat_edges(log_n, 8 << log_n, seed=1)
    n = 1 << log_n
    seq = degree_sequence(tail, head)
    pos = sequence_positions(seq, n - 1).astype(np.int64)
    pos = np.where(pos == 0xFFFFFFFF, len(seq), pos)  # absent -> sentinel
    m = len(seq)
    pt, ph = pos[tail], pos[head]
    lo = np.minimum(pt, ph)
    hi = np.maximum(pt, ph)
    dead = (lo == hi) | (hi >= m)
    lo = np.where(dead, m, lo)
    hi = np.where(dead, m, hi)
    return lo, hi, m, seq, tail, head


def sort_step(lo, hi, n):
    order = np.lexsort((hi, lo))
    lo, hi = lo[order], hi[order]
    prev_same = np.concatenate([[False], lo[1:] == lo[:-1]])
    prev_hi = np.concatenate([[n], hi[:-1]])
    lo = np.where(prev_same & (lo != n), prev_hi, lo)
    dead = lo >= hi
    lo = np.where(dead, n, lo)
    hi = np.where(dead, n, hi)
    return lo, hi


def jump_step(lo, hi, n, levels):
    f = np.full(n + 1, n, dtype=np.int64)
    np.minimum.at(f, lo, hi)
    tables = [f]
    for _ in range(levels - 1):
        tables.append(tables[-1][tables[-1]])
    for table in reversed(tables):
        nlo = table[lo]
        lo = np.where(nlo < hi, nlo, lo)
    return lo, hi


def run(variant, lo, hi, n, max_rounds=100000):
    live_log = {}
    rounds = 0
    while True:
        before = lo.copy()
        if variant == "sort":
            lo, hi = sort_step(lo, hi, n)
        elif variant.startswith("jump"):
            L = int(variant[4:])
            do_sort = rounds >= 7 and (rounds & (rounds + 1)) == 0
            if do_sort:
                lo, hi = sort_step(lo, hi, n)
            lo, hi = jump_step(lo, hi, n, L)
        elif variant.startswith("sj"):
            L = int(variant[2:])
            lo, hi = sort_step(lo, hi, n)
            lo, hi = jump_step(lo, hi, n, L)
        rounds += 1
        if rounds in (1, 2, 4, 8, 16, 32, 64):
            live_log[rounds] = int((lo != n).sum())
        if np.array_equal(lo, before) or rounds >= max_rounds:
            break
    parent = np.full(n + 1, n, dtype=np.int64)
    np.minimum.at(parent, lo, hi)
    return parent[:n], rounds, live_log, int((lo != n).sum())


def main():
    variants = sys.argv[1].split(",") if len(sys.argv) > 1 \
        else ["jump10", "sort", "sj1", "sj3"]
    sizes = [int(s) for s in (sys.argv[2].split(",") if len(sys.argv) > 2
                              else ["16", "18", "19"])]
    for log_n in sizes:
        lo0, hi0, m, seq, tail, head = links_of(log_n)
        from sheep_tpu.core.forest import build_forest
        want = build_forest(tail, head, seq)
        wparent = np.where(want.parent == 0xFFFFFFFF, m,
                           want.parent.astype(np.int64))
        for v in variants:
            parent, rounds, live_log, live = run(v, lo0.copy(), hi0.copy(), m)
            ok = bool(np.array_equal(parent, wparent))
            print(json.dumps({"variant": v, "log_n": log_n, "e": len(lo0),
                              "rounds": rounds, "live_final": live,
                              "live": live_log, "oracle_equal": ok}),
                  flush=True)


if __name__ == "__main__":
    main()
