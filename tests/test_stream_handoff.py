"""Streaming windowed handoff (ISSUE 8): the hybrid's tail consumes the
reduced live set as ascending hi-quantile windows, each folded through
the resumable native union-find while the next window is still in
flight.  Covered here: the W in {1, 2, 4, 8} parity sweep (bit-identical
parent+pst, equal ECV(down) vs the serial fetch), the accelerator window
queue (device hi-sort + _WindowStream) forced on the cpu backend, clean
serial fallback on a mid-stream fetch failure AND on a mid-fold failure,
the host-seq prep arm on/off, the non-immediate (reduced-multiset) pst
resolver path, and the driver's stream rung + its governor pricing."""

import numpy as np
import pytest

from sheep_tpu.core import build_forest, degree_sequence


@pytest.fixture
def stream_env(monkeypatch):
    monkeypatch.setenv("SHEEP_STREAM_HANDOFF", "1")
    for k in ("SHEEP_HANDOFF_WINDOWS", "SHEEP_STREAM_DEVICE_WINDOWS",
              "SHEEP_STREAM_HOST_SEQ", "SHEEP_HANDOFF_FACTOR",
              "SHEEP_OVERLAP_HANDOFF", "SHEEP_PACK_HANDOFF"):
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


def _serial_reference(tail, head, n, stream_env):
    from sheep_tpu.ops import build_graph_hybrid
    stream_env.setenv("SHEEP_STREAM_HANDOFF", "0")
    seq0, f0 = build_graph_hybrid(tail, head, n)
    stream_env.setenv("SHEEP_STREAM_HANDOFF", "1")
    return seq0, f0


def test_windowed_parity_sweep(stream_env):
    """W in {1, 2, 4, 8}: bit-identical parent+pst and equal ECV(down)
    vs the serial-fetch tail (the acceptance sweep)."""
    from sheep_tpu.ops import build_graph_hybrid
    n, tail, head = _graph()
    seq0, f0 = _serial_reference(tail, head, n, stream_env)
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


@pytest.mark.parametrize("packed", [False, True])
def test_device_window_queue_forced_on_cpu(stream_env, packed):
    """The accelerator transfer machinery — device hi-sort + the
    _WindowStream slice queue with prefetch depth 2 — forced on the cpu
    backend (the overlap tests' trick), packed and pair modes."""
    from sheep_tpu.ops import build_graph_hybrid
    n, tail, head = _graph()
    seq0, f0 = _serial_reference(tail, head, n, stream_env)
    stream_env.setenv("SHEEP_STREAM_DEVICE_WINDOWS", "1")
    stream_env.setenv("SHEEP_HANDOFF_WINDOWS", "4")
    # slice small enough that 4 windows get >= 1 slice each (the stream
    # caps W at the slice count)
    stream_env.setenv("SHEEP_OVERLAP_SLICE", "2048")
    if packed:
        stream_env.setenv("SHEEP_PACK_HANDOFF", "1")
    perf = {}
    seq, f = build_graph_hybrid(tail, head, n, perf=perf)
    assert perf.get("stream_mode") == "windowed", perf
    assert perf.get("packed_handoff") is packed
    assert perf.get("fetch_windows") == 4
    np.testing.assert_array_equal(seq, seq0)
    np.testing.assert_array_equal(f.parent, f0.parent)
    np.testing.assert_array_equal(f.pst_weight, f0.pst_weight)


def test_mid_stream_fetch_failure_raises(stream_env, monkeypatch):
    """A slice fetch dying mid-stream is a device-runtime error: it must
    reach the caller, not be swallowed by a slower host path."""
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
    with pytest.raises(RuntimeError, match="injected slice fault"):
        build_graph_hybrid(tail, head, n)
    assert calls["n"] == 3


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
