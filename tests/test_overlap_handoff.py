"""Overlapped speculative handoff (ops.build._SpecHandoff + the
reduce_and_fetch_links driver) — VERDICT r04 item 1.

The machinery is accelerator-targeted (default-on off-cpu) but fully
exercisable on the cpu backend by forcing SHEEP_OVERLAP_HANDOFF=1 with
tiny slice/min-size knobs: correctness must be oracle-exact through
every speculation outcome (complete, waited-out, restarted, abandoned,
unions of partial snapshots), because any snapshot — or union of
snapshots — preserves threshold connectivity (ops.forest proof).
"""

from __future__ import annotations

import numpy as np
import pytest

from sheep_tpu.core import build_forest, degree_sequence
from sheep_tpu.core.forest import native_or_none


def _oracle(tail, head):
    seq = degree_sequence(tail, head)
    return seq, build_forest(tail, head, seq)


def _graph(seed=90, n=400, e=6000):
    rng = np.random.default_rng(seed)
    tail = rng.integers(0, n, e).astype(np.uint32)
    head = rng.integers(0, n, e).astype(np.uint32)
    return tail, head


@pytest.fixture
def overlap_env(monkeypatch):
    monkeypatch.setenv("SHEEP_OVERLAP_HANDOFF", "1")
    monkeypatch.setenv("SHEEP_OVERLAP_MIN_MB", "0.0001")
    monkeypatch.setenv("SHEEP_OVERLAP_SLICE", "4096")
    # keep the loop from skipping rounds so the watch hook actually fires
    monkeypatch.delenv("SHEEP_HANDOFF_FACTOR", raising=False)
    return monkeypatch


def test_hybrid_overlap_oracle_exact(overlap_env):
    from sheep_tpu.ops import build_graph_hybrid

    tail, head = _graph()
    want_seq, want = _oracle(tail, head)
    seq, forest = build_graph_hybrid(tail, head, handoff_factor=2)
    np.testing.assert_array_equal(seq, want_seq)
    np.testing.assert_array_equal(forest.parent, want.parent)
    np.testing.assert_array_equal(forest.pst_weight, want.pst_weight)


def test_hybrid_overlap_matches_overlap_off(overlap_env):
    from sheep_tpu.ops import build_graph_hybrid

    tail, head = _graph(seed=91)
    seq_on, f_on = build_graph_hybrid(tail, head, handoff_factor=2)
    overlap_env.setenv("SHEEP_OVERLAP_HANDOFF", "0")
    seq_off, f_off = build_graph_hybrid(tail, head, handoff_factor=2)
    np.testing.assert_array_equal(seq_on, seq_off)
    np.testing.assert_array_equal(f_on.parent, f_off.parent)
    np.testing.assert_array_equal(f_on.pst_weight, f_off.pst_weight)


def test_reduce_and_fetch_spec_runs_and_is_exact(overlap_env):
    """Drive reduce_and_fetch_links directly and check the speculation
    actually engaged (spec_starts >= 1) and the handoff set rebuilds the
    oracle forest through the native union-find."""
    import jax.numpy as jnp
    from sheep_tpu.ops.build import (prepare_links, reduce_and_fetch_links,
                                     finish_native_host)

    overlap_env.setenv("SHEEP_OVERLAP_SPEC_FACTOR", "1000")
    tail, head = _graph(seed=92, n=1 << 10, e=1 << 14)
    n = 1 << 10
    want_seq, want = _oracle(tail, head)
    _, _, m, lo, hi, pst = prepare_links(
        jnp.asarray(tail, jnp.int32), jnp.asarray(head, jnp.int32), n)
    perf: dict = {}
    kind, a, b, live, rounds = reduce_and_fetch_links(
        lo, hi, n, stop_live=n, perf=perf)
    assert perf.get("spec_starts", 0) >= 1, perf
    assert "loop_s" in perf and "fetch_tail_s" in perf
    if kind == "device":  # converged before threshold — still checkable
        from sheep_tpu.ops.build import fetch_links_host
        a, b, _ = fetch_links_host(a, b, live, n)
    parent, pst_out = finish_native_host(
        np.asarray(a), np.asarray(b), n, np.asarray(pst, np.uint32)[:n])
    m = int(m)
    np.testing.assert_array_equal(parent[:m], want.parent)
    np.testing.assert_array_equal(pst_out[:m], want.pst_weight)


def test_union_of_snapshots_is_sound(overlap_env):
    """The correctness backbone of abandoned-partial reuse: feeding the
    union-find links from TWO different chunk generations (a complete
    later snapshot plus the full earlier one as 'kept partials') yields
    the identical forest."""
    import jax.numpy as jnp
    from sheep_tpu.ops.build import prepare_links, finish_native_host
    from sheep_tpu.ops.forest import reduce_links_hosted

    tail, head = _graph(seed=93, n=512, e=1 << 13)
    n = 512
    want_seq, want = _oracle(tail, head)
    _, _, m, lo, hi, pst = prepare_links(
        jnp.asarray(tail, jnp.int32), jnp.asarray(head, jnp.int32), n)
    snaps = []

    def watch(slo, shi, live):
        snaps.append((np.asarray(slo), np.asarray(shi), int(live)))
        return False

    lo2, hi2, live2, _, _ = reduce_links_hosted(lo, hi, n, stop_live=n,
                                                watch=watch)
    assert snaps, "watch hook never fired"
    early_lo, early_hi, early_live = snaps[0]
    final_lo = np.asarray(lo2)[:int(live2)]
    final_hi = np.asarray(hi2)[:int(live2)]
    mix_lo = np.concatenate([early_lo[:early_live], final_lo])
    mix_hi = np.concatenate([early_hi[:early_live], final_hi])
    keep = mix_lo < n
    parent, pst_out = finish_native_host(
        mix_lo[keep], mix_hi[keep], n, np.asarray(pst, np.uint32)[:n])
    m = int(m)
    np.testing.assert_array_equal(parent[:m], want.parent)
    np.testing.assert_array_equal(pst_out[:m], want.pst_weight)


def test_stream_fetcher_packed_and_pair_modes(overlap_env):
    """_StreamFetcher must deliver the exact snapshot bytes in both the
    6-byte-packed (n < 2^24) and int32-pair (n >= 2^24) modes."""
    import jax.numpy as jnp
    from sheep_tpu.ops.build import _StreamFetcher

    rng = np.random.default_rng(94)
    for n in ((1 << 20), (1 << 24) + 5):
        live = 9000
        pad = 1 << 14
        lo = np.full(pad, n, np.int64)
        hi = np.full(pad, n, np.int64)
        lo[:live] = rng.integers(0, n - 1, live)
        hi[:live] = rng.integers(0, n - 1, live)
        f = _StreamFetcher(jnp.asarray(lo, jnp.int32),
                           jnp.asarray(hi, jnp.int32), n, live,
                           slice_links=2048)
        f.join()
        assert f.finished() and not f.failed
        got_lo, got_hi = f.collect()
        keep = got_lo < n
        np.testing.assert_array_equal(got_lo[keep], lo[:live])
        np.testing.assert_array_equal(got_hi[keep], hi[:live])
        assert f.remaining_bytes() == 0


def test_stream_fetcher_non_pow2_slice_covers_all(overlap_env):
    """A non-power-of-two SHEEP_OVERLAP_SLICE must not skip tail links:
    the fetcher rounds the knob down to a pow2 so slices always tile the
    pow2-padded width (a dropped tail would mean a silently wrong
    forest)."""
    import jax.numpy as jnp
    from sheep_tpu.ops.build import _StreamFetcher

    n = 1 << 20
    pad = 1 << 14
    live = pad - 100  # live links close to the padded width
    rng = np.random.default_rng(96)
    lo = np.full(pad, n, np.int64)
    hi = np.full(pad, n, np.int64)
    lo[:live] = rng.integers(0, n - 1, live)
    hi[:live] = rng.integers(0, n - 1, live)
    f = _StreamFetcher(jnp.asarray(lo, jnp.int32), jnp.asarray(hi, jnp.int32),
                       n, live, slice_links=3000)  # not a pow2
    assert f.slice_len == 2048
    f.join()
    assert f.finished()
    got_lo, got_hi = f.collect()
    keep = got_lo < n
    np.testing.assert_array_equal(got_lo[keep], lo[:live])
    np.testing.assert_array_equal(got_hi[keep], hi[:live])


def test_stream_fetcher_abort_keeps_prefix(overlap_env):
    import jax.numpy as jnp
    from sheep_tpu.ops.build import _StreamFetcher

    n = 1 << 20
    pad = 1 << 14
    rng = np.random.default_rng(95)
    lo = rng.integers(0, n - 1, pad)
    hi = rng.integers(0, n - 1, pad)
    f = _StreamFetcher(jnp.asarray(lo, jnp.int32), jnp.asarray(hi, jnp.int32),
                       n, pad, slice_links=1024)
    f.abort()  # immediate abort: whatever slices landed must be a prefix
    assert f.failed is False, "abort must not poison a healthy stream"
    got_lo, got_hi = f.collect()
    k = len(got_lo)
    assert k % 1024 == 0 and k == f.done_slices * 1024
    np.testing.assert_array_equal(got_lo, lo[:k])
    np.testing.assert_array_equal(got_hi, hi[:k])


def test_spec_handoff_restart_policy():
    """The adaptive abandon/restart rule, unit-level: a fetch whose
    remaining bytes exceed 1.25x the fresh snapshot restarts; kept
    partial buffers survive into complete()."""
    from sheep_tpu.ops.build import _SpecHandoff

    class FakeFetcher:
        def __init__(self, remaining, done=1):
            self._remaining = remaining
            self.done_slices = done
            self.failed = False
            self.error = None
        def finished(self):
            return self._remaining == 0
        def remaining_bytes(self):
            return self._remaining
        def abort(self):
            pass
        def join(self):
            self._remaining = 0
        def fetched_bytes(self):
            return 6 * 1000
        def collect(self):
            return (np.zeros(10, np.int32), np.ones(10, np.int32))

    n = 1 << 16
    sp = _SpecHandoff(n)
    started = []
    sp._start = lambda lo, hi, live: started.append(live)  # type: ignore
    # active fetch with a huge remainder vs a still-large current
    # snapshot (above the min_bytes floor): abandon AND restart
    big_live = 2 * sp.min_bytes // sp.bpl
    sp.active = FakeFetcher(remaining=100 * sp.min_bytes)
    assert sp.on_chunk(None, None, big_live) is False
    assert sp.stats["spec_restarts"] == 1 and started == [big_live]
    # huge remainder vs a TINY snapshot: abandon, but the restart honors
    # the same min_bytes floor as first starts (ADVICE r05 — a restart
    # on a tiny snapshot pays a pack dispatch / fresh compile for less
    # than it saves)
    sp.active = FakeFetcher(remaining=10_000_000)
    assert sp.on_chunk(None, None, 1000) is False
    assert sp.stats["spec_restarts"] == 2 and started == [big_live]
    # finished fetch stops the loop
    sp.active = FakeFetcher(remaining=0)
    assert sp.on_chunk(None, None, 500) is True
    assert sp.stats["spec_stopped_loop"] is True


def test_overlap_disabled_on_cpu_by_default(monkeypatch):
    monkeypatch.delenv("SHEEP_OVERLAP_HANDOFF", raising=False)
    from sheep_tpu.ops.build import _overlap_enabled
    import jax
    if jax.devices()[0].platform == "cpu":
        assert _overlap_enabled() is False


@pytest.mark.skipif(native_or_none("auto") is None,
                    reason="native runtime unavailable")
def test_hybrid_overlap_rmat_larger(overlap_env):
    """A larger R-MAT through the full hybrid with speculation forced,
    multi-slice, factor 1 (longest loop, most chances to restart)."""
    from sheep_tpu.ops import build_graph_hybrid
    from sheep_tpu.utils import rmat_edges

    tail, head = rmat_edges(13, 8 << 13, seed=5)
    want_seq, want = _oracle(tail, head)
    seq, forest = build_graph_hybrid(tail, head, handoff_factor=1)
    np.testing.assert_array_equal(seq, want_seq)
    np.testing.assert_array_equal(forest.parent, want.parent)
    np.testing.assert_array_equal(forest.pst_weight, want.pst_weight)


def test_spec_wait_timeout_falls_back_serial(overlap_env):
    """A wedged stream (join watchdog fires) must fall back to the
    serial fetch, record mode=spec_wait_timeout, count the wasted
    bytes, and still produce the exact link set."""
    import sheep_tpu.ops.build as b

    n = 1 << 12
    rng = np.random.default_rng(97)
    pad = 1 << 13
    lo_np = np.full(pad, n, np.int64)
    hi_np = np.full(pad, n, np.int64)
    live = 6000
    lo_np[:live] = rng.integers(0, n - 1, live)
    hi_np[:live] = np.minimum(lo_np[:live] + 1, n - 1)
    import jax.numpy as jnp
    lo = jnp.asarray(lo_np, jnp.int32)
    hi = jnp.asarray(hi_np, jnp.int32)

    sp = b._SpecHandoff(n)

    class WedgedFetcher:
        failed = False
        error = None
        done_slices = 1
        def finished(self):
            return False
        def remaining_bytes(self):
            return 1  # tiny remainder -> complete() takes the wait path
        def join(self, timeout=None, mark_failed=True):
            if mark_failed:
                self.failed = True  # watchdog fired
            return True
        def abort(self, timeout=5.0):
            pass
        def fetched_bytes(self):
            return 3 << 20
        def collect(self):
            raise AssertionError("collect must not run on a wedged stream")

    sp.active = WedgedFetcher()
    lo_h, hi_h = sp.complete(lo, hi, live)
    assert sp.stats["spec_mode"] == "spec_wait_timeout"
    assert sp.stats["spec_wasted_mb"] >= 3.0
    # pairwise multiset check: both halves of every link must survive
    order_got = np.lexsort((hi_h, lo_h))
    order_want = np.lexsort((hi_np[:live], lo_np[:live]))
    np.testing.assert_array_equal(lo_h[order_got],
                                  lo_np[:live][order_want])
    np.testing.assert_array_equal(hi_h[order_got],
                                  hi_np[:live][order_want])
    assert len(lo_h) == len(hi_h) == live


def test_abort_slow_stream_does_not_poison(overlap_env):
    """abort() on a slow-but-healthy stream must not mark it failed or
    disable later speculation; landed slices stay collectable."""
    from sheep_tpu.ops.build import _SpecHandoff

    sp = _SpecHandoff(1 << 16)

    class SlowFetcher:
        failed = False
        error = None
        done_slices = 2
        def join(self, timeout=None, mark_failed=True):
            return True  # still draining, but abort passes mark_failed=False
        def abort(self, timeout=5.0):
            self.join(timeout, mark_failed=False)
        def fetched_bytes(self):
            return 2 << 20
        def collect(self):
            return (np.zeros(100, np.int32), np.ones(100, np.int32))

    sp.active = SlowFetcher()
    sp._abandon()  # a slow abort is not an error: nothing raises
    assert len(sp.kept) == 1, "landed partial slices must be kept"


def test_spec_fetch_error_reaches_the_caller(overlap_env):
    """A fetch thread that died on a device-runtime error must not be
    abandoned in silence: the next chunk hook re-raises its exception."""
    from sheep_tpu.ops.build import _SpecHandoff

    sp = _SpecHandoff(1 << 16)

    class DeadFetcher:
        failed = True
        error = RuntimeError("injected fetch fault")

    sp.active = DeadFetcher()
    with pytest.raises(RuntimeError, match="injected fetch fault"):
        sp.on_chunk(None, None, 1000)


def test_hybrid_overlap_pair_mode_large_n(overlap_env):
    """End-to-end hybrid at n >= 2^24 (sparse edges over a huge vertex
    space): the overlapped stream must take the int32-pair mode (no
    6-byte packing above 2^24) and stay oracle-exact."""
    n = (1 << 24) + 1000
    e = 60_000
    rng = np.random.default_rng(98)
    tail = rng.integers(0, n, e).astype(np.uint32)
    head = rng.integers(0, n, e).astype(np.uint32)
    from sheep_tpu.ops import build_graph_hybrid

    want_seq, want = _oracle(tail, head)
    overlap_env.setenv("SHEEP_OVERLAP_SPEC_FACTOR", "100000")
    seq, forest = build_graph_hybrid(tail, head, num_vertices=n,
                                     handoff_factor=1)
    np.testing.assert_array_equal(seq, want_seq)
    np.testing.assert_array_equal(forest.parent, want.parent)
    np.testing.assert_array_equal(forest.pst_weight, want.pst_weight)
