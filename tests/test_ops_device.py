"""Device kernels (sheep_tpu.ops) == sequential oracle (sheep_tpu.core).

The batched fixpoint formulation must produce the *identical* parent array
to the reference's sequential union-find insert loop on every input — this
is SURVEY §7's "hard part #1", tested here on adversarial shapes (stars and
paths exercise the chain/jump rewrites), random multigraphs with self-loops,
and the bundled hep-th graph.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from conftest import random_multigraph

from sheep_tpu import INVALID_JNID

from sheep_tpu.core import (
    build_forest, degree_sequence, merge_forests, edges_to_positions,
)
from sheep_tpu.core.forest import build_forest_links
from sheep_tpu.ops import (
    build_forest_device, degree_sequence_device, merge_forests_device,
    build_graph_device, forest_fixpoint,
)


def assert_forest_equal(got, want, msg=""):
    np.testing.assert_array_equal(got.parent, want.parent, err_msg=msg)
    np.testing.assert_array_equal(got.pst_weight, want.pst_weight, err_msg=msg)


def both_forests(tail, head):
    seq = degree_sequence(tail, head)
    want = build_forest(tail, head, seq)
    got = build_forest_device(tail, head, seq)
    return got, want


# --- adversarial structures -------------------------------------------------

def test_star_center_first():
    # Center eliminated first => elimination tree is a path: the worst case
    # for naive parallel union-find, handled by the star->chain rewrite.
    n = 64
    tail = np.zeros(n - 1, dtype=np.uint32)
    head = np.arange(1, n, dtype=np.uint32)
    seq = np.arange(n, dtype=np.uint32)  # identity order: center at pos 0
    want = build_forest(tail, head, seq)
    got = build_forest_device(tail, head, seq)
    assert_forest_equal(got, want)
    # depth-n chain must not take ~n rounds
    lo, hi = edges_to_positions(tail, head, seq)
    import jax.numpy as jnp
    _, rounds = forest_fixpoint(jnp.asarray(lo, jnp.int32),
                                jnp.asarray(hi, jnp.int32), n)
    assert int(rounds) < 20, f"star took {int(rounds)} rounds"


def test_path_graph():
    n = 100
    tail = np.arange(n - 1, dtype=np.uint32)
    head = np.arange(1, n, dtype=np.uint32)
    assert_forest_equal(*both_forests(tail, head))


def test_complete_graph():
    n = 24
    tail, head = np.triu_indices(n, k=1)
    assert_forest_equal(*both_forests(tail.astype(np.uint32),
                                      head.astype(np.uint32)))


def test_crossing_links_counterexample():
    # The case that breaks naive batched min-attach: link (1,4)'s root lags
    # behind while (3,5) would commit parent[3]=5; truth is parent[3]=4.
    seq = np.arange(6, dtype=np.uint32)
    tail = np.array([1, 2, 1, 3], dtype=np.uint32)
    head = np.array([2, 3, 4, 5], dtype=np.uint32)
    want = build_forest(tail, head, seq)
    got = build_forest_device(tail, head, seq)
    assert want.parent[3] == 4
    assert_forest_equal(got, want)


def test_binary_staircase():
    # Nested components merging at every scale.
    rng = np.random.default_rng(7)
    n = 128
    edges = []
    for width in (2, 4, 8, 16, 32, 64, 128):
        for s in range(0, n, width):
            edges.append((s, s + width - 1))
    tail = np.array([a for a, _ in edges], dtype=np.uint32)
    head = np.array([b for _, b in edges], dtype=np.uint32)
    assert_forest_equal(*both_forests(tail, head))


# --- randomized equivalence -------------------------------------------------

@pytest.mark.parametrize("trial", range(40))
def test_random_multigraph_device_equals_oracle(trial):
    rng = np.random.default_rng(1000 + trial)
    tail, head = random_multigraph(rng)
    assert_forest_equal(*both_forests(tail, head), msg=f"trial {trial}")


@pytest.mark.parametrize("trial", range(10))
def test_random_identity_sequence(trial):
    # Non-degree orders must work too (fileSequence / -s flag paths).
    rng = np.random.default_rng(2000 + trial)
    tail, head = random_multigraph(rng, n_max=60, e_max=200)
    n = int(max(tail.max(), head.max())) + 1
    seq = rng.permutation(n).astype(np.uint32)
    want = build_forest(tail, head, seq)
    got = build_forest_device(tail, head, seq)
    assert_forest_equal(got, want, msg=f"trial {trial}")


# --- device sequence --------------------------------------------------------

@pytest.mark.parametrize("trial", range(15))
def test_degree_sequence_device(trial):
    rng = np.random.default_rng(3000 + trial)
    tail, head = random_multigraph(rng)
    np.testing.assert_array_equal(
        degree_sequence_device(tail, head), degree_sequence(tail, head))


def test_fused_build_matches_pipeline():
    rng = np.random.default_rng(42)
    tail, head = random_multigraph(rng, n_max=80, e_max=400)
    seq, forest = build_graph_device(tail, head)
    want_seq = degree_sequence(tail, head)
    np.testing.assert_array_equal(seq, want_seq)
    assert_forest_equal(forest, build_forest(tail, head, want_seq))


# --- device merge -----------------------------------------------------------

@pytest.mark.parametrize("parts", [2, 3, 8])
def test_merge_device_equals_oracle(parts):
    rng = np.random.default_rng(500 + parts)
    tail, head = random_multigraph(rng, n_max=50, e_max=300)
    seq = degree_sequence(tail, head)
    cuts = np.linspace(0, len(tail), parts + 1).astype(int)
    partials = [
        build_forest(tail[a:b], head[a:b], seq, max_vid=int(max(tail.max(), head.max())))
        for a, b in zip(cuts[:-1], cuts[1:])
    ]
    want = merge_forests(*partials)
    got = merge_forests_device(*partials)
    assert_forest_equal(got, want)
    # and the merged tree equals the whole-graph tree
    assert_forest_equal(got, build_forest(tail, head, seq))


# --- hep-th golden ----------------------------------------------------------

def test_hepth_device_equals_oracle(hep_edges):
    seq = degree_sequence(hep_edges.tail, hep_edges.head)
    want = build_forest(hep_edges.tail, hep_edges.head, seq)
    got = build_forest_device(hep_edges.tail, hep_edges.head, seq)
    assert_forest_equal(got, want)


def test_hepth_fixpoint_rounds(hep_edges):
    import jax.numpy as jnp
    seq = degree_sequence(hep_edges.tail, hep_edges.head)
    lo, hi = edges_to_positions(hep_edges.tail, hep_edges.head, seq)
    _, rounds = forest_fixpoint(jnp.asarray(lo, jnp.int32),
                                jnp.asarray(hi, jnp.int32), len(seq))
    assert int(rounds) < 64, f"hep-th took {int(rounds)} fixpoint rounds"


@pytest.mark.parametrize("seed", range(10))
def test_hosted_fixpoint_matches_oracle(seed):
    # The chunked host-orchestrated fixpoint (production path on hardware)
    # must produce the oracle parent array exactly.
    from sheep_tpu.ops.forest import forest_fixpoint_hosted

    rng = np.random.default_rng(900 + seed)
    tail, head = random_multigraph(rng, 80, 400)
    seq = degree_sequence(tail, head)
    want = build_forest(tail, head, seq, impl="python")
    from sheep_tpu.core.forest import edges_to_positions
    lo, hi = edges_to_positions(tail, head, seq)
    n = len(seq)
    pst_only = hi >= n
    lo_d = np.where(pst_only, n, lo)
    hi_d = np.where(pst_only, n, hi)
    parent, rounds = forest_fixpoint_hosted(
        jnp.asarray(lo_d, jnp.int32), jnp.asarray(hi_d, jnp.int32), n)
    parent = np.asarray(parent).astype(np.int64)
    got = np.full(n, INVALID_JNID, dtype=np.uint32)
    got[parent < n] = parent[parent < n].astype(np.uint32)
    np.testing.assert_array_equal(got, want.parent)


@pytest.mark.parametrize("seed,handoff", [(0, 2), (1, 2), (2, 1), (3, 1000)])
def test_build_graph_hybrid_matches_oracle(seed, handoff):
    # handoff=1000 exercises the handoff branch immediately (stop_live
    # huge -> first chunk hands off); small handoffs converge on device.
    from sheep_tpu.ops import build_graph_hybrid

    rng = np.random.default_rng(950 + seed)
    tail, head = random_multigraph(rng, 200, 1200)
    want_seq = degree_sequence(tail, head)
    want = build_forest(tail, head, want_seq)
    seq, forest = build_graph_hybrid(tail, head, handoff_factor=handoff)
    np.testing.assert_array_equal(seq, want_seq)
    np.testing.assert_array_equal(forest.parent, want.parent)
    np.testing.assert_array_equal(forest.pst_weight, want.pst_weight)


@pytest.mark.parametrize("handoff", [2, 1000])
def test_build_graph_hybrid_explicit_host_edges(handoff):
    # the accelerator configuration: seq/pst recomputed host-side from the
    # caller's edge copy instead of fetched from the device (auto-detect is
    # gated off on the cpu backend, so pass host_edges explicitly here)
    from sheep_tpu.ops import build_graph_hybrid

    rng = np.random.default_rng(955)
    tail, head = random_multigraph(rng, 200, 1200)
    want_seq = degree_sequence(tail, head)
    want = build_forest(tail, head, want_seq)
    seq, forest = build_graph_hybrid(tail, head, handoff_factor=handoff,
                                     host_edges=(tail, head))
    np.testing.assert_array_equal(seq, want_seq)
    np.testing.assert_array_equal(forest.parent, want.parent)
    np.testing.assert_array_equal(forest.pst_weight, want.pst_weight)


@pytest.mark.parametrize("with_host_edges", [False, True])
@pytest.mark.parametrize("handoff", [2, 1000])
def test_build_graph_hybrid_given_seq(with_host_edges, handoff):
    # the `-s` fast path: no device histogram/sort, links map through the
    # given position table; a SUBSET sequence exercises the absent-vid pst
    # contract (edges to absent vids count toward pst, never the tree)
    from sheep_tpu.ops import build_graph_hybrid

    rng = np.random.default_rng(957)
    tail, head = random_multigraph(rng, 200, 1200)
    full = degree_sequence(tail, head)
    seq = full[: max(2, len(full) * 2 // 3)]
    want = build_forest(tail, head, seq,
                        max_vid=int(max(tail.max(), head.max())))
    he = (tail, head) if with_host_edges else None
    out_seq, forest = build_graph_hybrid(tail, head, handoff_factor=handoff,
                                         host_edges=he, seq=seq)
    np.testing.assert_array_equal(out_seq, seq)
    np.testing.assert_array_equal(forest.parent, want.parent)
    np.testing.assert_array_equal(forest.pst_weight, want.pst_weight)


@pytest.mark.parametrize("given", [False, True])
@pytest.mark.parametrize("handoff", [2, 1000])
def test_build_graph_hybrid_prefetch_failure_raises(monkeypatch, handoff,
                                                    given):
    # with host_edges the device skips its pst scatter (with_pst=False) in
    # both the degree-sort and given-seq branches; if the host prefetch
    # then dies, its error must reach the caller (no silent slower path);
    # where no prefetch runs, the result is bit-identical to the oracle
    import sheep_tpu.ops.build as build_mod

    calls = []

    def boom(*a, **k):
        calls.append(1)
        raise RuntimeError("prefetch failure injected by test")

    monkeypatch.setattr(build_mod, "_host_seq_pst", boom)
    rng = np.random.default_rng(962)
    tail, head = random_multigraph(rng, 200, 1200)
    full = degree_sequence(tail, head)
    # given-seq uses a SUBSET order so the absent-vid pst contract is in
    # play on the lazy path too
    seq_in = full[: max(2, len(full) * 2 // 3)] if given else None
    want_seq = seq_in if given else full
    want = build_forest(tail, head, want_seq,
                        max_vid=int(max(tail.max(), head.max())))
    try:
        seq, forest = build_mod.build_graph_hybrid(
            tail, head, handoff_factor=handoff, host_edges=(tail, head),
            seq=seq_in)
    except RuntimeError as exc:
        assert calls and "prefetch failure injected" in str(exc)
        return
    assert not calls, "a failed prefetch was swallowed"
    np.testing.assert_array_equal(seq, want_seq)
    np.testing.assert_array_equal(forest.parent, want.parent)
    np.testing.assert_array_equal(forest.pst_weight, want.pst_weight)


def test_build_graph_hybrid_device_inputs_no_host_copy():
    # device-array inputs without host_edges exercise the d2h prefetch
    # branch (numpy inputs auto-use the host recompute path)
    import jax.numpy as jnp
    from sheep_tpu.ops import build_graph_hybrid

    rng = np.random.default_rng(960)
    tail, head = random_multigraph(rng, 200, 1200)
    want_seq = degree_sequence(tail, head)
    want = build_forest(tail, head, want_seq)
    n = int(max(tail.max(), head.max())) + 1
    seq, forest = build_graph_hybrid(
        jnp.asarray(tail), jnp.asarray(head), n, handoff_factor=1000)
    np.testing.assert_array_equal(seq, want_seq)
    np.testing.assert_array_equal(forest.parent, want.parent)
    np.testing.assert_array_equal(forest.pst_weight, want.pst_weight)


def test_host_seq_pst_matches_device():
    from sheep_tpu.ops.build import _host_seq_pst, prepare_links
    import jax.numpy as jnp

    rng = np.random.default_rng(961)
    tail, head = random_multigraph(rng, 300, 2000)  # includes self-loops
    n = int(max(tail.max(), head.max())) + 1
    seq_d, _, m, _, _, pst_d = prepare_links(
        jnp.asarray(tail), jnp.asarray(head), n)
    seq_h, pst_h = _host_seq_pst(tail, head, n)
    m = int(m)
    assert len(seq_h) == m
    np.testing.assert_array_equal(seq_h, np.asarray(seq_d)[:m])
    np.testing.assert_array_equal(pst_h, np.asarray(pst_d))


def test_pack_links_6b_roundtrip():
    from sheep_tpu.ops.forest import pack_links_6b, unpack_links_6b
    import jax.numpy as jnp

    rng = np.random.default_rng(962)
    lo = rng.integers(0, (1 << 24) - 1, 5000).astype(np.int32)
    hi = rng.integers(0, (1 << 24) - 1, 5000).astype(np.int32)
    buf = np.asarray(pack_links_6b(jnp.asarray(lo), jnp.asarray(hi)))
    assert buf.dtype == np.uint8 and buf.shape == (5000, 6)
    lo2, hi2 = unpack_links_6b(buf)
    np.testing.assert_array_equal(lo2, lo)
    np.testing.assert_array_equal(hi2, hi)


def test_build_graph_hybrid_packed_handoff(monkeypatch):
    # force the packed 6-byte handoff (default-off on the cpu backend)
    from sheep_tpu.ops import build_graph_hybrid

    monkeypatch.setenv("SHEEP_PACK_HANDOFF", "1")
    rng = np.random.default_rng(963)
    tail, head = random_multigraph(rng, 300, 2000)
    want_seq = degree_sequence(tail, head)
    want = build_forest(tail, head, want_seq)
    seq, forest = build_graph_hybrid(tail, head, handoff_factor=1000)
    np.testing.assert_array_equal(seq, want_seq)
    np.testing.assert_array_equal(forest.parent, want.parent)
    np.testing.assert_array_equal(forest.pst_weight, want.pst_weight)


def test_build_graph_device_rmat_oracle():
    from sheep_tpu.ops import build_graph_device
    from sheep_tpu.utils import rmat_edges

    tail, head = rmat_edges(12, 4 << 12, seed=3)
    want_seq = degree_sequence(tail, head)
    want = build_forest(tail, head, want_seq)
    seq, forest = build_graph_device(tail, head)
    np.testing.assert_array_equal(seq, want_seq)
    np.testing.assert_array_equal(forest.parent, want.parent)
    np.testing.assert_array_equal(forest.pst_weight, want.pst_weight)


def test_depth_tier_rule():
    """Pin the three-tier lifting-depth boundaries (PERF_NOTES round-4
    A/B): light at full width inside the schedule, +2 mid, +6 below an
    eighth, capped at log2(n)."""
    from sheep_tpu.ops.forest import _depth_tier

    pad, levels, first, cap = 1 << 20, 10, 4, 22
    assert _depth_tier(pad, pad, True, levels, first, cap) == first
    # outside the schedule, full width no longer gets the light tier
    assert _depth_tier(pad, pad, False, levels, first, cap) == levels + 2
    assert _depth_tier(pad // 2, pad, True, levels, first, cap) == levels + 2
    assert _depth_tier(pad // 8 + 1, pad, True, levels, first, cap) \
        == levels + 2
    assert _depth_tier(pad // 8, pad, True, levels, first, cap) == levels + 6
    # small-n cap beats the escalation
    assert _depth_tier(100, 4096, False, levels, first, 9) == 9


def test_vremap_roundtrip_and_composition():
    """vremap_compact relabels monotonically, back-maps exactly, and the
    back tables compose the way reduce_links_hosted chains them."""
    from sheep_tpu.ops.forest import vremap_compact, vremap_back

    rng = np.random.default_rng(41)
    n = 1 << 18
    verts = np.sort(rng.choice(n - 1, size=600, replace=False))
    lo = verts[rng.integers(0, 500, 2048)].astype(np.int32)
    hi = (lo + 1 + rng.integers(0, 50, 2048)).astype(np.int32)
    dead = rng.random(2048) < 0.3
    lo[dead] = n
    hi[dead] = n
    nc1 = 2 * len(lo)
    lo1, hi1, back1 = vremap_compact(jnp.asarray(lo), jnp.asarray(hi),
                                     n, nc1)
    lo1_np, hi1_np = np.asarray(lo1), np.asarray(hi1)
    # monotone relabel: order within live links is preserved, dead -> nc1
    live = lo < n
    assert np.all(lo1_np[live] < hi1_np[live])
    assert np.all(lo1_np[~live] == nc1) and np.all(hi1_np[~live] == nc1)
    rlo, rhi = vremap_back(lo1, hi1, back1)
    np.testing.assert_array_equal(np.asarray(rlo), lo)
    np.testing.assert_array_equal(np.asarray(rhi), hi)
    # second remap into a smaller space + composed back table
    nc2 = 1 << 12
    lo2, hi2, back2 = vremap_compact(lo1, hi1, nc1, nc2)
    back_total = back1[back2]
    rlo2, rhi2 = vremap_back(lo2, hi2, back_total)
    np.testing.assert_array_equal(np.asarray(rlo2), lo)
    np.testing.assert_array_equal(np.asarray(rhi2), hi)


@pytest.mark.parametrize("seed", range(3))
def test_hosted_fixpoint_vremap_sparse_matches_dense(seed, monkeypatch):
    """A sparse live set over a large position space triggers the vertex
    remap (2*cols <= n/4 with n > 2^16); parents must be bit-identical to
    the remap-disabled run and the remap must actually fire."""
    import sheep_tpu.ops.forest as F

    rng = np.random.default_rng(1300 + seed)
    n = 1 << 17
    # chains among ~1500 scattered positions: stays sparse, needs several
    # chunks, and cols pads to the 4096 floor => remap fires immediately
    verts = np.sort(rng.choice(n - 1, size=1500, replace=False))
    idx = rng.integers(0, 1400, 3000)
    lo = verts[idx].astype(np.int32)
    hi = verts[idx + 1 + rng.integers(0, 90, 3000)].astype(np.int32)
    bad = lo >= hi
    lo[bad] = n
    hi[bad] = n

    calls = {"remaps": 0}
    real = F.vremap_compact

    def counting(*a, **k):
        calls["remaps"] += 1
        return real(*a, **k)

    monkeypatch.setattr(F, "vremap_compact", counting)
    monkeypatch.setenv("SHEEP_VREMAP", "1")
    p_on, _ = F.forest_fixpoint_hosted(jnp.asarray(lo), jnp.asarray(hi), n)
    assert calls["remaps"] >= 1, "remap did not trigger on the sparse case"
    monkeypatch.setenv("SHEEP_VREMAP", "0")
    p_off, _ = F.forest_fixpoint_hosted(jnp.asarray(lo), jnp.asarray(hi), n)
    np.testing.assert_array_equal(np.asarray(p_on), np.asarray(p_off))


def test_sort_links_branches_agree(monkeypatch):
    """The packed-int64 and 2-key variadic branches of sort_links must
    produce identical lexicographic results (the packed branch is the cpu
    default, the 2-key branch the accelerator default — tests force cpu,
    so without this check the 2-key branch would be untested).  Eager
    calls: the gate is read at trace time, so a jitted caller would keep
    whichever branch it compiled first."""
    from sheep_tpu.ops.forest import sort_links

    rng = np.random.default_rng(77)
    n = (1 << 22) + 3
    lo = rng.integers(0, n, 5000).astype(np.int32)
    hi = rng.integers(0, n, 5000).astype(np.int32)
    dead = rng.random(5000) < 0.2
    lo[dead] = n
    hi[dead] = n
    out = {}
    for mode in ("0", "1"):
        monkeypatch.setenv("SHEEP_SORT_PACK64", mode)
        slo, shi = sort_links(jnp.asarray(lo), jnp.asarray(hi))
        out[mode] = (np.asarray(slo), np.asarray(shi))
        assert out[mode][0].dtype == np.int32
    np.testing.assert_array_equal(out["0"][0], out["1"][0])
    np.testing.assert_array_equal(out["0"][1], out["1"][1])
    order = np.lexsort((hi, lo))
    np.testing.assert_array_equal(out["1"][0], lo[order])
    np.testing.assert_array_equal(out["1"][1], hi[order])


def test_degree_order_branches_agree(monkeypatch):
    """degree_order's packed and 2-key branches must agree (same gate as
    sort_links; tests run cpu = packed, accelerators get 2-key)."""
    import jax

    from sheep_tpu.ops.sort import degree_order

    rng = np.random.default_rng(78)
    deg = rng.integers(0, 50, 4096).astype(np.int32)
    deg[rng.random(4096) < 0.3] = 0
    out = {}
    for mode in ("0", "1"):
        monkeypatch.setenv("SHEEP_SORT_PACK64", mode)
        jax.clear_caches()  # the gate is trace-time; drop the cached branch
        seq, pos, m = degree_order(jnp.asarray(deg))
        out[mode] = (np.asarray(seq), np.asarray(pos), int(m))
    np.testing.assert_array_equal(out["0"][0], out["1"][0])
    np.testing.assert_array_equal(out["0"][1], out["1"][1])
    assert out["0"][2] == out["1"][2]
