"""Pipelined chunk dispatch (SHEEP_PIPELINE_CHUNKS, round 5): the host
loop keeps the next chunk in flight while the previous chunk's stats
resolve, compacting one chunk late.  Must be bit-identical to the
classic loop through every exit path (convergence, stop_live, vremap
drain) — the accelerator default is ON, so the CPU
tests force the gate."""

from __future__ import annotations

import numpy as np
import pytest

from conftest import random_multigraph

from sheep_tpu.core import build_forest, degree_sequence


def _links(tail, head, n):
    import jax.numpy as jnp
    from sheep_tpu.ops.build import prepare_links
    return prepare_links(jnp.asarray(tail, jnp.int32),
                         jnp.asarray(head, jnp.int32), n)


@pytest.mark.parametrize("trial", range(4))
def test_pipelined_fixpoint_matches_classic(monkeypatch, trial):
    from sheep_tpu.ops.forest import forest_fixpoint_hosted

    rng = np.random.default_rng(4200 + trial)
    tail, head = random_multigraph(rng, n_max=300, e_max=4000)
    n = int(max(tail.max(initial=0), head.max(initial=0))) + 1
    _, _, _, lo, hi, _ = _links(tail, head, n)
    monkeypatch.setenv("SHEEP_PIPELINE_CHUNKS", "0")
    classic, r0 = forest_fixpoint_hosted(lo, hi, n)
    monkeypatch.setenv("SHEEP_PIPELINE_CHUNKS", "1")
    piped, r1 = forest_fixpoint_hosted(lo, hi, n)
    np.testing.assert_array_equal(np.asarray(classic), np.asarray(piped))


@pytest.mark.parametrize("factor", [1, 4])
def test_pipelined_stop_live_links_rebuild_oracle(monkeypatch, factor):
    """Early stop one chunk late still returns a connectivity-complete
    link set: rebuilding the forest from it matches the oracle."""
    from sheep_tpu.core.forest import build_forest_links
    from sheep_tpu.ops.forest import reduce_links_hosted

    rng = np.random.default_rng(4300 + factor)
    tail, head = random_multigraph(rng, n_max=400, e_max=6000)
    n = int(max(tail.max(initial=0), head.max(initial=0))) + 1
    want_seq = degree_sequence(tail, head)
    want = build_forest(tail, head, want_seq)
    m = len(want_seq)
    _, _, _, lo, hi, pst = _links(tail, head, n)
    monkeypatch.setenv("SHEEP_PIPELINE_CHUNKS", "1")
    lo2, hi2, live, rounds, converged = reduce_links_hosted(
        lo, hi, n, stop_live=factor * n)
    lo_h = np.asarray(lo2)
    hi_h = np.asarray(hi2)
    keep = lo_h < n
    forest = build_forest_links(
        lo_h[keep].astype(np.uint32), hi_h[keep].astype(np.uint32), n,
        pst=np.asarray(pst, np.uint32)[:n])
    np.testing.assert_array_equal(forest.parent[:m], want.parent)
    np.testing.assert_array_equal(forest.pst_weight[:m], want.pst_weight)


def test_pipelined_hybrid_with_overlap(monkeypatch):
    """Pipelined dispatch and the accelerator's device window queue
    forced together on cpu, end to end: reduce rounds (factor 2), then
    the hi-sorted table streams in windows into the resumable fold."""
    from sheep_tpu.ops import build_graph_hybrid

    monkeypatch.setenv("SHEEP_PIPELINE_CHUNKS", "1")
    monkeypatch.setenv("SHEEP_STREAM_DEVICE_WINDOWS", "1")
    monkeypatch.setenv("SHEEP_HANDOFF_WINDOWS", "4")
    monkeypatch.setenv("SHEEP_OVERLAP_SLICE", "4096")
    from sheep_tpu.utils import rmat_edges
    tail, head = rmat_edges(13, 8 << 13, seed=9)
    want_seq = degree_sequence(tail, head)
    want = build_forest(tail, head, want_seq)
    seq, forest = build_graph_hybrid(tail, head, handoff_factor=2)
    np.testing.assert_array_equal(seq, want_seq)
    np.testing.assert_array_equal(forest.parent, want.parent)
    np.testing.assert_array_equal(forest.pst_weight, want.pst_weight)


def test_pipelined_vremap_drain(monkeypatch):
    """Sparse links over a big position space force the vertex remap;
    under pipelining the loop must drain and still match the oracle
    (the remap path the hybrid's partial builds exercise)."""
    from sheep_tpu.ops.forest import forest_fixpoint_hosted

    rng = np.random.default_rng(4400)
    n = 1 << 17  # big position space
    e = 2000     # sparse links -> 2*cols <= n/4 fires
    import jax.numpy as jnp
    lo_np = rng.integers(0, n - 1, e)
    hi_np = np.minimum(lo_np + 1 + rng.integers(0, 64, e), n - 1)
    keep = lo_np < hi_np
    lo_np, hi_np = lo_np[keep], hi_np[keep]
    lo = jnp.asarray(lo_np, jnp.int32)
    hi = jnp.asarray(hi_np, jnp.int32)
    monkeypatch.setenv("SHEEP_PIPELINE_CHUNKS", "0")
    classic, _ = forest_fixpoint_hosted(lo, hi, n)
    monkeypatch.setenv("SHEEP_PIPELINE_CHUNKS", "1")
    piped, _ = forest_fixpoint_hosted(lo, hi, n)
    np.testing.assert_array_equal(np.asarray(classic), np.asarray(piped))


def test_pipeline_gate_defaults(monkeypatch):
    import jax
    from sheep_tpu.ops.forest import _pipeline_chunks

    monkeypatch.delenv("SHEEP_PIPELINE_CHUNKS", raising=False)
    if jax.devices()[0].platform == "cpu":
        assert _pipeline_chunks() is False
    monkeypatch.setenv("SHEEP_PIPELINE_CHUNKS", "1")
    assert _pipeline_chunks() is True
    monkeypatch.setenv("SHEEP_PIPELINE_CHUNKS", "0")
    assert _pipeline_chunks() is False


def test_pipe_width_gate_boundaries():
    """Pin the width gate: 4x-compacted AND <= 2^17 (PERF_NOTES round-5
    A/B: ungated stale-width compaction cost +29.5% on instant-stats
    cpu; the RTT-vs-compute crossover is ~1e5 slots)."""
    from sheep_tpu.ops.forest import _pipe_width_ok

    pad = 1 << 20
    assert _pipe_width_ok(1 << 17, pad)
    assert not _pipe_width_ok((1 << 17) + 1, pad)     # absolute cap
    assert not _pipe_width_ok(1 << 17, 1 << 18)       # not 4x-compacted
    assert _pipe_width_ok(1 << 16, 1 << 18)
    assert _pipe_width_ok(4096, 1 << 14)


def test_pipelined_branch_fires_and_matches(monkeypatch):
    """At a size where the gate genuinely fires (dense rmat: plateau
    width ~pad/8 <= 2^17), the pipelined run must take the in-flight
    path (observed via a fixpoint_chunk call trace whose consumption
    lags by one chunk is invisible — so assert on the gate math from
    the traced widths) and stay bit-identical to classic."""
    import jax
    import sheep_tpu.ops.forest as F
    from sheep_tpu.utils import rmat_edges
    from sheep_tpu.ops.build import prepare_links
    import jax.numpy as jnp

    n = 1 << 14
    tail, head = rmat_edges(14, 8 * n, seed=21)
    _, _, _, lo, hi, _ = prepare_links(
        jnp.asarray(tail, jnp.int32), jnp.asarray(head, jnp.int32), n)
    jax.block_until_ready((lo, hi))
    widths = []
    orig = F.fixpoint_chunk

    def traced(lo, hi, n_, lv, j):
        widths.append(int(lo.shape[0]))
        return orig(lo, hi, n_, lv, j)

    monkeypatch.setattr(F, "fixpoint_chunk", traced)
    monkeypatch.setenv("SHEEP_PIPELINE_CHUNKS", "0")
    classic, _ = F.forest_fixpoint_hosted(lo, hi, n)
    pad = max(widths)
    assert any(F._pipe_width_ok(w, pad) for w in widths), \
        f"test size never reaches the gate: widths={widths}"
    widths.clear()
    monkeypatch.setenv("SHEEP_PIPELINE_CHUNKS", "1")
    piped, _ = F.forest_fixpoint_hosted(lo, hi, n)
    np.testing.assert_array_equal(np.asarray(classic), np.asarray(piped))
