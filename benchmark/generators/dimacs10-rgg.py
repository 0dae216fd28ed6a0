"""The DIMACS10 random geometric graph (``rgg_n_2_<k>_s0``, Holtgrewe,
Sanders and Schulz, IPDPS 2010), made on the host with numpy.

``2^log2_vertices`` points lie uniform in the unit square, drawn from
``graph_seed``; two points are joined by an edge when they lie closer
than ``radius_factor * sqrt(ln n / n)``.  Each such pair is one record:
no self-loops, no duplicates, no wrap-around.  The pairs are found on a
grid whose cells are at least the radius wide, so a point meets only
the points of its own cell and of the four cells after it (east and the
three to the north), a vectorised pass per neighbour slot.

As in ``kron.py``, the structure comes from ``graph_seed`` and
``--seed`` picks only the vertex relabelling and the record shuffle: every
seed is the same graph.  It is not quite the same work, since the degree
sequence breaks its many ties by vid, so the labels pick among sequences
and so among elimination trees (heights within 0.5% of each other).
"""

from __future__ import annotations

import numpy as np

#: the configuration keys this generator reads
KEYS = {"log2_vertices", "radius_factor", "graph_seed"}


def radius(n: int, radius_factor: float) -> float:
    """The connection radius of ``n`` points: factor * sqrt(ln n / n)."""
    return radius_factor * np.sqrt(np.log(n) / n)


def points(n: int, graph_seed: int) -> np.ndarray:
    """[n, 2] float64 points uniform in [0, 1)^2."""
    return np.random.default_rng(graph_seed).random((n, 2))


def _hits(x, y, p, q, r2):
    """The pairs (p, q) of sorted positions closer than sqrt(r2)."""
    dx, dy = x[p] - x[q], y[p] - y[q]
    near = dx * dx + dy * dy < r2
    return p[near], q[near]


def pairs(xy: np.ndarray, r: float) -> tuple[np.ndarray, np.ndarray]:
    """Every unordered pair (i, j) of rows of ``xy`` whose squared
    distance is below ``r * r``, each once, as int64 index arrays."""
    n = len(xy)
    r2 = r * r
    g = max(1, int(np.floor((1.0 - 1e-9) / r)))  # cells a side, each >= r
    side = g + 2  # a ring of empty cells: neighbours need no bounds test
    cx = np.minimum((xy[:, 0] * g).astype(np.int64), g - 1) + 1
    cy = np.minimum((xy[:, 1] * g).astype(np.int64), g - 1) + 1
    cell = cx * side + cy
    order = np.argsort(cell, kind="stable")
    cell = cell[order]
    x, y = xy[order, 0], xy[order, 1]
    count = np.bincount(cell, minlength=side * side)
    start = np.cumsum(count) - count
    end = start + count
    got_p, got_q = [], []
    pos = np.arange(n, dtype=np.int64)
    # the cell's own later points
    act = pos
    t = 1
    while len(act):
        act = act[act + t < end[cell[act]]]
        p, q = _hits(x, y, act, act + t, r2)
        got_p.append(p)
        got_q.append(q)
        t += 1
    # east, north-west, north, north-east
    for d in (1, side - 1, side, side + 1):
        act = pos
        nb = start[cell + d]
        nc = count[cell + d]
        t = 0
        while len(act):
            keep = nc > t
            act, nb, nc = act[keep], nb[keep], nc[keep]
            p, q = _hits(x, y, act, nb + t, r2)
            got_p.append(p)
            got_q.append(q)
            t += 1
    p, q = np.concatenate(got_p), np.concatenate(got_q)
    return order[p], order[q]


def edges(config: dict, seed: int):
    """(tail, head) uint32 host arrays: one record per edge, the
    vertices relabelled and the records shuffled by ``seed``."""
    n = 1 << int(config["log2_vertices"])
    i, j = pairs(points(n, int(config["graph_seed"])),
                 radius(n, float(config["radius_factor"])))
    rng = np.random.default_rng(int(seed))
    label = rng.permutation(n).astype(np.uint32)
    order = rng.permutation(len(i))
    return label[i[order]], label[j[order]]
