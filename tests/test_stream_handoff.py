"""Streaming windowed handoff: the hybrid's tail consumes the reduced
live set as ascending hi-quantile windows, each folded through the
resumable native union-find while the next window is still in flight.
Covered here: the W in {1, 2, 4, 8} parity sweep (bit-identical
parent+pst, equal ECV(down) vs the host oracle), the accelerator window
queue (device hi-sort + _WindowStream) forced on the cpu backend, the
_WindowStream transfer itself (packed and pair modes, slice tiling,
the prefetch bound, abort), a fetch failure AND a fold failure raising
to the caller, the host-seq prep arm on/off, the non-immediate
(reduced-multiset) pst resolver path, the soundness of a union of link
snapshots, and the driver's stream rung + its governor pricing."""

import time

import numpy as np
import pytest

from sheep_tpu.core import build_forest, degree_sequence


@pytest.fixture
def stream_env(monkeypatch):
    for k in ("SHEEP_HANDOFF_WINDOWS", "SHEEP_STREAM_DEVICE_WINDOWS",
              "SHEEP_STREAM_HOST_SEQ", "SHEEP_HANDOFF_FACTOR",
              "SHEEP_PACK_HANDOFF", "SHEEP_OVERLAP_SLICE"):
        monkeypatch.delenv(k, raising=False)
    return monkeypatch


def _graph(log_n=12, seed=3):
    from sheep_tpu.utils.synth import rmat_edges
    n = 1 << log_n
    tail, head = rmat_edges(log_n, 4 * n, seed=seed)
    return n, tail, head


def _ecv_down(seq, forest, tail, head, parts=4):
    from sheep_tpu.partition import Partition, evaluate_partition
    part = Partition.from_forest(seq, forest, num_parts=parts)
    rep = evaluate_partition(part.parts, tail, head, seq, num_parts=parts)
    return int(rep.ecv_down)


def _oracle(tail, head):
    seq = degree_sequence(tail, head)
    return seq, build_forest(tail, head, seq)


def _table(n, live, pad, seed, sort_hi=False):
    """A pow2-padded host link table: ``live`` random links, then dead
    sentinel slots (n)."""
    rng = np.random.default_rng(seed)
    lo = np.full(pad, n, np.int64)
    hi = np.full(pad, n, np.int64)
    lo[:live] = rng.integers(0, n - 1, live)
    hi[:live] = rng.integers(0, n - 1, live)
    if sort_hi:
        order = np.argsort(hi[:live], kind="stable")
        lo[:live], hi[:live] = lo[:live][order], hi[:live][order]
    return lo, hi


def _stream(lo, hi, n, live, slice_links, windows):
    import jax.numpy as jnp
    from sheep_tpu.ops.build import _WindowStream
    return _WindowStream(jnp.asarray(lo, jnp.int32),
                         jnp.asarray(hi, jnp.int32), n, live,
                         slice_links, windows)


def _drain(s, n):
    """Every window of the stream, in order, dead slots masked."""
    parts = [s.window(k) for k in range(s.windows)]
    lo = np.concatenate([p[0] for p in parts])
    hi = np.concatenate([p[1] for p in parts])
    keep = lo < n
    return lo[keep], hi[keep]


def test_windowed_parity_sweep(stream_env):
    """W in {1, 2, 4, 8}: bit-identical parent+pst and equal ECV(down)
    vs the host oracle (the acceptance sweep)."""
    from sheep_tpu.ops import build_graph_hybrid
    n, tail, head = _graph()
    seq0, f0 = _oracle(tail, head)
    ecv0 = _ecv_down(seq0, f0, tail, head)
    for w in (1, 2, 4, 8):
        stream_env.setenv("SHEEP_HANDOFF_WINDOWS", str(w))
        perf = {}
        seq, f = build_graph_hybrid(tail, head, n, perf=perf)
        assert perf.get("stream_mode") == "windowed", perf
        assert perf.get("fetch_windows") == w
        np.testing.assert_array_equal(seq, seq0)
        np.testing.assert_array_equal(f.parent, f0.parent)
        np.testing.assert_array_equal(f.pst_weight, f0.pst_weight)
        assert _ecv_down(seq, f, tail, head) == ecv0


def _sparse_huge_n(n=(1 << 24) + 1000, e=60_000, seed=98):
    """Sparse edges over a vertex space past 2^24, where the 6-byte
    packing cannot hold a vertex id."""
    rng = np.random.default_rng(seed)
    tail = rng.integers(0, n, e).astype(np.uint32)
    head = rng.integers(0, n, e).astype(np.uint32)
    return n, tail, head


@pytest.mark.parametrize("graph,factor,packed", [
    ("rmat12", None, False),
    ("rmat12", None, True),
    # a larger R-MAT with real reduce rounds before the windows
    ("rmat13", "1", True),
    # n >= 2^24: the stream must take the int32-pair mode even when
    # packing is asked for
    ("sparse-n2^24", "1", True)])
def test_device_window_queue_forced_on_cpu(stream_env, graph, factor,
                                           packed):
    """The accelerator transfer machinery — device hi-sort + the
    _WindowStream slice queue with prefetch depth 2 — forced on the cpu
    backend, packed and pair modes."""
    from sheep_tpu.ops import build_graph_hybrid
    if graph == "rmat12":
        n, tail, head = _graph()
    elif graph == "rmat13":
        n, tail, head = _graph(log_n=13, seed=5)
    else:
        n, tail, head = _sparse_huge_n()
    seq0, f0 = _oracle(tail, head)
    stream_env.setenv("SHEEP_STREAM_DEVICE_WINDOWS", "1")
    stream_env.setenv("SHEEP_HANDOFF_WINDOWS", "4")
    # slice small enough that 4 windows get >= 1 slice each (the stream
    # caps W at the slice count)
    stream_env.setenv("SHEEP_OVERLAP_SLICE", "512")
    if factor is not None:
        stream_env.setenv("SHEEP_HANDOFF_FACTOR", factor)
    if packed:
        stream_env.setenv("SHEEP_PACK_HANDOFF", "1")
    perf = {}
    seq, f = build_graph_hybrid(tail, head, n, perf=perf)
    assert perf.get("stream_mode") == "windowed", perf
    assert perf.get("packed_handoff") is (packed and n < (1 << 24))
    assert perf.get("fetch_windows") == 4
    np.testing.assert_array_equal(seq, seq0)
    np.testing.assert_array_equal(f.parent, f0.parent)
    np.testing.assert_array_equal(f.pst_weight, f0.pst_weight)


@pytest.mark.parametrize("through", ["hybrid", "window"])
def test_mid_stream_fetch_failure_raises(stream_env, monkeypatch, through):
    """A slice fetch dying mid-stream is a device-runtime error: it must
    reach the caller — out of the whole hybrid build, and out of
    _WindowStream.window for the window that needed the slice."""
    import sheep_tpu.ops.build as B
    from sheep_tpu.ops import build_graph_hybrid
    n, tail, head = _graph()
    stream_env.setenv("SHEEP_STREAM_DEVICE_WINDOWS", "1")
    stream_env.setenv("SHEEP_HANDOFF_WINDOWS", "4")
    stream_env.setenv("SHEEP_OVERLAP_SLICE", "4096")
    real = B._slice_rows
    calls = {"n": 0}

    def flaky(buf, start, length):
        calls["n"] += 1
        if calls["n"] == 3:
            raise RuntimeError("injected slice fault")
        return real(buf, start, length)

    monkeypatch.setattr(B, "_slice_rows", flaky)
    if through == "hybrid":
        with pytest.raises(RuntimeError, match="injected slice fault"):
            build_graph_hybrid(tail, head, n)
    else:
        lo, hi = _table(n, 16000, 1 << 14, seed=99, sort_hi=True)
        s = _stream(lo, hi, n, 16000, 2048, 4)
        with pytest.raises(RuntimeError, match="injected slice fault"):
            for k in range(s.windows):
                s.window(k)
        s.abort()
        assert not s._thread.is_alive()
    assert calls["n"] == 3


@pytest.mark.parametrize("windows", [1, 4])
def test_window_stream_packed_and_pair_modes(stream_env, windows):
    """_WindowStream's windows, in order, deliver the exact table in both
    the 6-byte-packed (n < 2^24) and int32-pair (n >= 2^24) modes."""
    stream_env.setenv("SHEEP_PACK_HANDOFF", "1")
    for n in ((1 << 20), (1 << 24) + 5):
        live = 9000
        lo, hi = _table(n, live, 1 << 14, seed=94, sort_hi=True)
        s = _stream(lo, hi, n, live, 2048, windows)
        assert s.packed is (n < (1 << 24))
        assert s.windows == windows
        got_lo, got_hi = _drain(s, n)
        np.testing.assert_array_equal(got_lo, lo[:live])
        np.testing.assert_array_equal(got_hi, hi[:live])
        assert s.done_slices == s.total_slices and not s.failed


@pytest.mark.parametrize("windows", [1, 3])
def test_window_stream_non_pow2_slice_covers_all(stream_env, windows):
    """A non-power-of-two SHEEP_OVERLAP_SLICE must not skip tail links:
    the stream rounds the knob down to a pow2 so slices always tile the
    pow2-padded width (a dropped tail would mean a silently wrong
    forest)."""
    n = 1 << 20
    pad = 1 << 14
    live = pad - 100  # live links close to the padded width
    lo, hi = _table(n, live, pad, seed=96, sort_hi=True)
    s = _stream(lo, hi, n, live, 3000, windows)  # not a pow2
    assert s.slice_len == 2048
    got_lo, got_hi = _drain(s, n)
    np.testing.assert_array_equal(got_lo, lo[:live])
    np.testing.assert_array_equal(got_hi, hi[:live])


def test_window_stream_abort_keeps_prefix(stream_env):
    """With no consumer the fetch thread stops PREFETCH windows ahead of
    the fold; abort then ends it cleanly, the landed slices a prefix of
    the table and the stream not marked failed."""
    n = 1 << 20
    pad = 1 << 14
    lo, hi = _table(n, pad, pad, seed=95)
    s = _stream(lo, hi, n, pad, 1024, 8)  # 16 slices, 2 per window
    ahead = s._cuts[1 + s.PREFETCH]  # windows 0..PREFETCH may land
    deadline = time.monotonic() + 60
    while s.done_slices < ahead and time.monotonic() < deadline:
        time.sleep(0.01)
    time.sleep(0.2)
    assert s.done_slices == ahead, "the prefetch bound did not hold"
    s.abort()
    assert not s._thread.is_alive()
    assert s.failed is False and s.error is None
    got_lo, got_hi = s.collect_range(0, s.done_slices)
    k = ahead * 1024
    np.testing.assert_array_equal(got_lo, lo[:k])
    np.testing.assert_array_equal(got_hi, hi[:k])


def test_abort_slow_stream_does_not_poison(stream_env, monkeypatch):
    """abort() on a slow-but-healthy stream ends the thread within one
    slice: no live thread is left behind, and the stream is not marked
    failed."""
    import sheep_tpu.ops.build as B
    real = B._slice_rows

    def slow(buf, start, length):
        time.sleep(0.2)
        return real(buf, start, length)

    monkeypatch.setattr(B, "_slice_rows", slow)
    n = 1 << 20
    lo, hi = _table(n, 1 << 13, 1 << 13, seed=97)
    s = _stream(lo, hi, n, 1 << 13, 512, 4)  # 16 slow slices
    s.abort(timeout=5.0)
    assert not s._thread.is_alive()
    assert s.failed is False and s.error is None
    assert s.done_slices < s.total_slices


def test_mid_fold_failure_raises(stream_env, monkeypatch):
    """The host-side branch too: a fold block raising mid-window reaches
    the caller."""
    import sheep_tpu.core.forest as cf
    from sheep_tpu.ops import build_graph_hybrid
    n, tail, head = _graph()
    stream_env.setenv("SHEEP_HANDOFF_WINDOWS", "4")
    real = cf.links_fold
    calls = {"n": 0}

    def flaky_fold(n_, pst=None, impl="auto"):
        fold = real(n_, pst, impl)
        orig_block = fold.block

        def block(lo, hi):
            calls["n"] += 1
            if calls["n"] == 2:
                raise RuntimeError("injected fold fault")
            return orig_block(lo, hi)

        fold.block = block
        return fold

    monkeypatch.setattr(cf, "links_fold", flaky_fold)
    with pytest.raises(RuntimeError, match="injected fold fault"):
        build_graph_hybrid(tail, head, n)


def test_host_seq_arm_parity(stream_env):
    """The host-seq prep (native counting-sort sequence + device mapping
    only) and the device-seq prep produce bit-identical outputs, and the
    perf record says which tail ran."""
    from sheep_tpu.ops import build_graph_hybrid
    n, tail, head = _graph(seed=11)
    stream_env.setenv("SHEEP_STREAM_HOST_SEQ", "1")
    seq_a, f_a = build_graph_hybrid(tail, head, n)
    stream_env.setenv("SHEEP_STREAM_HOST_SEQ", "0")
    seq_b, f_b = build_graph_hybrid(tail, head, n)
    np.testing.assert_array_equal(seq_a, seq_b)
    np.testing.assert_array_equal(f_a.parent, f_b.parent)
    np.testing.assert_array_equal(f_a.pst_weight, f_b.pst_weight)
    want_seq = degree_sequence(tail, head)
    want = build_forest(tail, head, want_seq)
    np.testing.assert_array_equal(seq_a, want_seq)
    np.testing.assert_array_equal(f_a.parent, want.parent)
    np.testing.assert_array_equal(f_a.pst_weight, want.pst_weight)


def test_reduced_multiset_uses_prep_pst(stream_env):
    """A small handoff factor forces real reduce rounds (the multiset is
    rewritten), so the fold must consume the prep-time pst resolver, not
    accumulate — still bit-identical."""
    from sheep_tpu.ops import build_graph_hybrid
    n, tail, head = _graph(seed=7)
    want_seq = degree_sequence(tail, head)
    want = build_forest(tail, head, want_seq)
    stream_env.setenv("SHEEP_HANDOFF_FACTOR", "2")
    stream_env.setenv("SHEEP_HANDOFF_WINDOWS", "4")
    perf = {}
    seq, f = build_graph_hybrid(tail, head, n, perf=perf)
    np.testing.assert_array_equal(seq, want_seq)
    np.testing.assert_array_equal(f.parent, want.parent)
    np.testing.assert_array_equal(f.pst_weight, want.pst_weight)


def test_given_seq_partial_stays_exact(stream_env):
    """An externally given PARTIAL sequence (absent vids -> pst-only
    links that never reach the stream) must keep the absent-vid pst
    contract under the windowed tail."""
    from sheep_tpu.ops import build_graph_hybrid
    n, tail, head = _graph(seed=5)
    full = degree_sequence(tail, head)
    sub = full[: len(full) // 2]
    want = build_forest(tail, head, sub, max_vid=n - 1)
    stream_env.setenv("SHEEP_HANDOFF_WINDOWS", "4")
    seq, f = build_graph_hybrid(tail, head, n, seq=sub)
    np.testing.assert_array_equal(seq, sub)
    np.testing.assert_array_equal(f.parent, want.parent)
    np.testing.assert_array_equal(f.pst_weight, want.pst_weight)


def test_union_of_snapshots_is_sound():
    """Feeding the union-find links from TWO chunk generations (an early
    snapshot, stopped at a larger live count, plus the final reduced
    set) yields the identical forest: every chunk round preserves
    threshold connectivity, so does a union of its outputs."""
    import jax.numpy as jnp
    from sheep_tpu.core.forest import build_forest_links
    from sheep_tpu.ops.build import prepare_links
    from sheep_tpu.ops.forest import reduce_links_hosted

    n = 512
    rng = np.random.default_rng(93)
    tail = rng.integers(0, n, 1 << 13).astype(np.uint32)
    head = rng.integers(0, n, 1 << 13).astype(np.uint32)
    want_seq, want = _oracle(tail, head)
    _, _, m, lo, hi, pst = prepare_links(
        jnp.asarray(tail, jnp.int32), jnp.asarray(head, jnp.int32), n)
    e_lo, e_hi, e_live, e_rounds, _ = reduce_links_hosted(
        lo, hi, n, stop_live=4 * n)
    f_lo, f_hi, f_live, f_rounds, _ = reduce_links_hosted(
        lo, hi, n, stop_live=n)
    assert 1 <= e_rounds < f_rounds and e_live > f_live
    mix_lo = np.concatenate([np.asarray(e_lo)[:int(e_live)],
                             np.asarray(f_lo)[:int(f_live)]])
    mix_hi = np.concatenate([np.asarray(e_hi)[:int(e_live)],
                             np.asarray(f_hi)[:int(f_live)]])
    keep = mix_lo < n
    forest = build_forest_links(
        mix_lo[keep].astype(np.uint32), mix_hi[keep].astype(np.uint32), n,
        pst=np.asarray(pst, np.uint32)[:n])
    m = int(m)
    np.testing.assert_array_equal(forest.parent[:m], want.parent)
    np.testing.assert_array_equal(forest.pst_weight[:m], want.pst_weight)


def test_stream_rung_oracle_exact_and_windowed(monkeypatch):
    """The driver's stream rung folds the checkpointable link table
    window-by-window (O(n + window) beyond the input) and matches the
    oracle; shrinking the window forces multiple blocks."""
    from sheep_tpu.runtime import RuntimeConfig, build_graph_resilient
    import sheep_tpu.resources.governor as gov_mod
    n, tail, head = _graph(log_n=11, seed=9)
    want_seq = degree_sequence(tail, head)
    want = build_forest(tail, head, want_seq)
    monkeypatch.setattr(gov_mod, "SPILL_BLOCK", 1024)
    cfg = RuntimeConfig(ladder=("stream",))
    seq, forest = build_graph_resilient(tail, head, config=cfg)
    np.testing.assert_array_equal(seq, want_seq)
    np.testing.assert_array_equal(forest.parent, want.parent)
    np.testing.assert_array_equal(forest.pst_weight, want.pst_weight)
    windows = [e for e in cfg.events if e[0] == "stream-window"]
    assert len(windows) > 2, windows


def test_governor_prices_stream_between_host_and_spill(monkeypatch):
    """Tight budgets route host -> stream before spill: the stream rung
    is priced O(n + window) beyond the input, below the host rung's
    16-bytes-per-link int64 cast, above nothing it needs to yield to
    but the memory floor."""
    import sheep_tpu.resources.governor as gov_mod
    from sheep_tpu.resources.governor import ResourceGovernor, \
        rung_peak_nbytes
    n, links = 1 << 20, 1 << 23
    host_est = rung_peak_nbytes("host", n, links)
    stream_est = rung_peak_nbytes("stream", n, links)
    spill_est = rung_peak_nbytes("spill", n, links)
    assert spill_est < stream_est < host_est
    monkeypatch.setattr(gov_mod, "rss_bytes", lambda: 0)
    gov = ResourceGovernor(mem_budget=(host_est + stream_est) // 2)
    rungs, _ = gov.plan_rungs(["host", "stream", "spill"], n, links)
    assert rungs == ["stream", "spill"]
    tight = ResourceGovernor(mem_budget=spill_est // 2)
    rungs, _ = tight.plan_rungs(["host", "stream", "spill"], n, links)
    assert rungs == ["spill"]  # the floor always survives
