"""Native C++ runtime vs the numpy/python oracle — exact equivalence."""

import numpy as np
import pytest

from sheep_tpu import native, INVALID_JNID
from sheep_tpu.core.forest import (
    Forest, build_forest, build_forest_links, edges_to_positions,
    merge_forests)
from sheep_tpu.core.sequence import degree_sequence, sequence_positions
from sheep_tpu.partition.tree_partition import (
    TreePartitionOptions, forward_partition, node_weights, partition_forest)

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native runtime not built")


def _rand_graph(rng, n, m):
    tail = rng.integers(0, n, m).astype(np.uint32)
    head = rng.integers(0, n, m).astype(np.uint32)
    return tail, head


@pytest.mark.parametrize("seed", range(8))
def test_build_forest_matches_oracle(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(5, 200))
    m = int(rng.integers(0, 4 * n))
    tail, head = _rand_graph(rng, n, m)
    seq = degree_sequence(tail, head)
    ours = build_forest(tail, head, seq, impl="native")
    oracle = build_forest(tail, head, seq, impl="python")
    np.testing.assert_array_equal(ours.parent, oracle.parent)
    np.testing.assert_array_equal(ours.pst_weight, oracle.pst_weight)


def test_edges_to_links_matches_oracle():
    rng = np.random.default_rng(3)
    tail, head = _rand_graph(rng, 100, 400)
    seq = degree_sequence(tail, head)
    pos = sequence_positions(seq)
    lo_n, hi_n = native.edges_to_links(tail, head, pos)
    lo_o, hi_o = edges_to_positions(tail, head, seq)
    # native preserves record order and so does the oracle
    np.testing.assert_array_equal(lo_n.astype(np.int64), lo_o)
    np.testing.assert_array_equal(hi_n.astype(np.int64), hi_o)


@pytest.mark.parametrize("seed", range(6))
def test_merge_matches_direct_build(seed):
    """Partial builds + native merge == whole-graph build (associativity)."""
    rng = np.random.default_rng(100 + seed)
    n, m = 80, 300
    tail, head = _rand_graph(rng, n, m)
    seq = degree_sequence(tail, head)
    k = int(rng.integers(2, 5))
    cuts = np.linspace(0, m, k + 1).astype(int)
    partials = [
        build_forest(tail[a:b], head[a:b], seq, max_vid=n - 1, impl="native")
        for a, b in zip(cuts[:-1], cuts[1:])
    ]
    merged = merge_forests(*partials)
    whole = build_forest(tail, head, seq, max_vid=n - 1, impl="python")
    np.testing.assert_array_equal(merged.parent, whole.parent)
    np.testing.assert_array_equal(merged.pst_weight, whole.pst_weight)


@pytest.mark.parametrize("seed", range(8))
def test_forward_partition_matches_oracle(seed):
    rng = np.random.default_rng(200 + seed)
    n = int(rng.integers(10, 300))
    m = int(rng.integers(n, 5 * n))
    tail, head = _rand_graph(rng, n, m)
    seq = degree_sequence(tail, head)
    forest = build_forest(tail, head, seq, impl="python")
    for np_ in (2, 3, 7):
        ours = partition_forest(forest, np_, impl="native")
        ref = partition_forest(forest, np_, impl="python")
        np.testing.assert_array_equal(ours, ref)


def test_partial_sequence_contract_matches():
    """Edges to vids absent from seq count toward pst (never a link) — the
    reference's forever-uninserted neighbor (jtree.cpp:47-49) — and all
    implementations must agree, including vids beyond the position table."""
    tail = np.array([0, 0, 1, 3, 5], dtype=np.uint32)
    head = np.array([1, 2, 3, 3, 0], dtype=np.uint32)  # 2,5 absent; 3-3 loop
    seq = np.array([0, 1, 3], dtype=np.uint32)
    a = build_forest(tail, head, seq, max_vid=5, impl="python")
    b = build_forest(tail, head, seq, max_vid=5, impl="native")
    np.testing.assert_array_equal(a.parent, b.parent)
    np.testing.assert_array_equal(a.pst_weight, b.pst_weight)
    # 0-1 links; 0-2 pst-only; 1-3 links; 3-3 dropped; 5-0 pst-only at 0
    np.testing.assert_array_equal(a.pst_weight, [3, 1, 0])
    np.testing.assert_array_equal(a.parent, [1, 2, INVALID_JNID])
    # max_vid understated: vids beyond the table are still "absent", not OOB
    c = build_forest(tail, head, seq, max_vid=3, impl="python")
    d = build_forest(tail, head, seq, max_vid=3, impl="native")
    np.testing.assert_array_equal(c.pst_weight, a.pst_weight)
    np.testing.assert_array_equal(d.pst_weight, a.pst_weight)


def test_forward_partition_overweight_raises():
    forest = Forest(np.array([1, INVALID_JNID], dtype=np.uint32),
                    np.array([100, 1], dtype=np.uint32))
    w = node_weights(forest, TreePartitionOptions())
    with pytest.raises(ValueError):
        native.forward_partition(forest.parent, w, 10)


def test_degree_histogram():
    rng = np.random.default_rng(5)
    tail, head = _rand_graph(rng, 50, 200)
    deg = native.degree_histogram(tail, head, 50)
    ref = np.bincount(tail, minlength=50) + np.bincount(head, minlength=50)
    np.testing.assert_array_equal(deg, ref.astype(np.int64))


@pytest.mark.parametrize("seed", range(6))
def test_degree_sequence_counting_sort(seed):
    from sheep_tpu.core.sequence import degree_sequence_from_degrees

    rng = np.random.default_rng(300 + seed)
    deg = rng.integers(0, 10, int(rng.integers(1, 200))).astype(np.int64)
    nat = native.degree_sequence_from_degrees(deg)
    ref = degree_sequence_from_degrees(deg, impl="python")
    np.testing.assert_array_equal(nat, ref)


def test_forward_partition_corrupt_parent_raises():
    # A parent entry that is neither INVALID nor < n (e.g. from a corrupt
    # .tre file) must be rejected, not dereferenced (sheep_native.cpp rc=-3;
    # the reference dies on such input via live asserts, lib/jdata.h:36-40).
    parent = np.array([1, 7, INVALID_JNID], dtype=np.uint32)
    w = np.ones(3, dtype=np.int64)
    with pytest.raises(ValueError, match="corrupt"):
        native.forward_partition(parent, w, 10)


def test_degree_histogram_out_of_range_vid_raises():
    tail = np.array([0, 99], dtype=np.uint32)
    head = np.array([1, 1], dtype=np.uint32)
    with pytest.raises(ValueError, match="out of range"):
        native.degree_histogram(tail, head, 50)


# ---------------------------------------------------------------------------
# resumable link fold (streaming windowed handoff, round 7):
# sheep_build_forest_links_begin/_block/_finish and its python twin
# ---------------------------------------------------------------------------


def _rand_links(rng, n, m, pst_only_frac=0.05):
    a = rng.integers(0, n, m)
    b = rng.integers(0, n, m)
    keep = a != b
    lo = np.minimum(a, b)[keep].astype(np.int64)
    hi = np.maximum(a, b)[keep].astype(np.int64)
    po = rng.random(len(lo)) < pst_only_frac
    hi[po] = INVALID_JNID  # pst-only links (absent endpoint)
    return lo, hi


@pytest.mark.parametrize("seed", range(6))
@pytest.mark.parametrize("blocks", [1, 2, 4, 8])
def test_links_fold_block_parity(seed, blocks):
    """The resumable fold over ANY ascending-hi block split — including
    cuts landing inside an equal-hi group — is bit-identical to the
    monolithic build: native and python twins, pst accumulated in-fold
    and precomputed."""
    from sheep_tpu.core.forest import PyLinksFold
    rng = np.random.default_rng(800 + seed)
    n = int(rng.integers(50, 400))
    lo, hi = _rand_links(rng, n, int(rng.integers(10, 6 * n)))
    want = build_forest_links(lo, hi, n, impl="python")
    order = np.argsort(hi, kind="stable")
    lo_s, hi_s = lo[order], hi[order]
    cuts = [(len(lo_s) * k) // blocks for k in range(blocks + 1)]
    for make in (lambda pst: native.LinksFold(n, pst),
                 lambda pst: PyLinksFold(n, pst)):
        for pst in (None, want.pst_weight):
            fold = make(pst)
            for a, b in zip(cuts[:-1], cuts[1:]):
                fold.block(lo_s[a:b], hi_s[a:b])
            parent, pst_out = fold.finish()
            np.testing.assert_array_equal(parent, want.parent)
            np.testing.assert_array_equal(pst_out, want.pst_weight)


def test_links_fold_out_of_order_window_raises():
    """An out-of-order window would silently build a different forest —
    both twins must refuse it loudly."""
    from sheep_tpu.core.forest import PyLinksFold
    n = 10
    for fold in (native.LinksFold(n), PyLinksFold(n)):
        fold.block(np.array([3], np.int64), np.array([7], np.int64))
        with pytest.raises(ValueError, match="ascend"):
            fold.block(np.array([1], np.int64), np.array([2], np.int64))


def test_links_fold_malformed_lo_raises():
    from sheep_tpu.core.forest import PyLinksFold
    n = 10
    for fold in (native.LinksFold(n), PyLinksFold(n)):
        with pytest.raises(ValueError):
            fold.block(np.array([12], np.int64), np.array([13], np.int64))


def test_links_fold_equal_hi_group_split_exact():
    """A window boundary INSIDE one hi-group is exact by construction
    (distinct roots adopt once, repeats no-op) — pin it explicitly."""
    from sheep_tpu.core.forest import PyLinksFold
    lo = np.array([0, 1, 2, 3], np.int64)
    hi = np.array([5, 5, 5, 5], np.int64)
    n = 6
    want = build_forest_links(lo, hi, n, impl="python")
    for make in (lambda: native.LinksFold(n), lambda: PyLinksFold(n)):
        fold = make()
        fold.block(lo[:2], hi[:2])
        fold.block(lo[2:], hi[2:])  # same hi=5 group continues
        parent, pst_out = fold.finish()
        np.testing.assert_array_equal(parent, want.parent)
        np.testing.assert_array_equal(pst_out, want.pst_weight)


def _pre_oracle(tail, head, seq):
    # Brute force meetKid semantics (lib/jnode.h:174-176): replay the
    # reference's sequential insert with unions deferred per vertex.
    pos = {int(v): i for i, v in enumerate(seq)}
    n = len(seq)
    uf = list(range(n))

    def find(x):
        while uf[x] != x:
            x = uf[x]
        return x

    pre = np.zeros(n, dtype=np.uint32)
    parent = np.full(n, -1, dtype=np.int64)
    adj = {}
    for t, h in zip(tail.tolist(), head.tolist()):
        if t == h or t not in pos or h not in pos:
            continue
        a, b = pos[t], pos[h]
        lo, hi = min(a, b), max(a, b)
        adj.setdefault(hi, []).append(lo)
    for h in range(n):
        adopted = []
        for lo in adj.get(h, []):
            r = find(lo)
            pre[r] += 1
            if r != h and parent[r] == -1:
                parent[r] = h
                adopted.append(r)
        for r in adopted:
            uf[r] = h
    return pre


@pytest.mark.parametrize("seed", range(8))
def test_pre_weights_native_python_oracle_agree(seed):
    from sheep_tpu.core.forest import pre_weights
    from sheep_tpu.core.sequence import degree_sequence

    rng = np.random.default_rng(700 + seed)
    tail, head = _rand_graph(rng, 40, 160)
    seq = degree_sequence(tail, head)
    ref = _pre_oracle(tail, head, seq)
    np.testing.assert_array_equal(
        pre_weights(tail, head, seq, impl="python"), ref)
    np.testing.assert_array_equal(
        pre_weights(tail, head, seq, impl="native"), ref)


def test_library_rebuilds_when_source_hash_changes(tmp_path):
    """The loaded library is keyed on a hash of its source and Makefile:
    an edit yields a new name that is built fresh, and a stale or foreign
    ``libsheep_native.so`` beside it is never the one loaded."""
    import ctypes
    import os
    import shutil

    src = os.path.dirname(native.__file__)
    shutil.copy(os.path.join(src, "Makefile"), tmp_path / "Makefile")
    shutil.copytree(os.path.join(src, "src"), tmp_path / "src")
    (tmp_path / "libsheep_native.so").write_bytes(b"not a library")
    first = native.ensure_built(str(tmp_path))
    assert first == native.so_path(str(tmp_path))
    assert os.path.basename(first) != "libsheep_native.so"
    with open(tmp_path / "src" / "sheep_native.cpp", "a") as f:
        f.write("\n// edited\n")
    second = native.so_path(str(tmp_path))
    assert second != first and not os.path.exists(second)
    assert native.ensure_built(str(tmp_path)) == second
    native._bind(ctypes.CDLL(second))  # a complete, loadable library
