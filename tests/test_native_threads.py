"""Threaded native kernels (ISSUE 14): deterministic parallel partials.

The OpenMP arm decomposes every hot kernel into per-thread partials
(forests over slices / bucket runs, histogram adds) merged through the
SAME associative fold the tournament runs — so parent+pst must be
BIT-identical to the single-thread build for every thread count, on any
host.  Covered here: the forced-T sweep (fused edges build, links
build, the resumable fold, histograms, the counting sort) with equal
ECV(down); partial-merge parity against the PyLinksFold python oracle;
merge-bracket independence (which PROVES a checkpoint may resume under
a DIFFERENT thread count — the partial-merge bracket is not part of the
input identity, demonstrated by an actual cross-T kill/resume);
kill-during-threaded-fold at every block boundary; the affinity clamp
(forcing T compute threads onto fewer granted cores resolves down
unless SHEEP_NATIVE_OVERSUB=1 opts in); the governor's thread plan
(SHEEP_LEG_CORES cap, memory-budget veto, operator pin); cgroup
cpu-quota detection; and the threads field on native.* spans plus the
ladder.plan explanation.
"""

import json
import os

import numpy as np
import pytest

from sheep_tpu import native
from sheep_tpu.core.forest import PyLinksFold, build_forest, \
    edges_to_positions, merge_forests
from sheep_tpu.core.sequence import degree_sequence

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native runtime not built")

#: forced-T arms need the OpenMP build; a serial build runs everything
#: at threads=1 by contract (the Makefile fallback), so the arms SKIP
#: rather than fail
needs_omp = pytest.mark.skipif(
    not (native.available() and native.omp_compiled()),
    reason="library compiled without OpenMP — forced-T arms skip")


@pytest.fixture
def thread_env(monkeypatch):
    # floor 0 engages the threaded path on test-sized inputs; OVERSUB
    # lets forced T exceed this host's granted cores (the clamp is
    # tested separately)
    monkeypatch.setenv("SHEEP_NATIVE_THREAD_FLOOR", "0")
    monkeypatch.setenv("SHEEP_NATIVE_OVERSUB", "1")
    for k in ("SHEEP_NATIVE_THREADS", "SHEEP_MEM_BUDGET",
              "SHEEP_LEG_CORES", "SHEEP_NATIVE_BLOCKED"):
        monkeypatch.delenv(k, raising=False)
    yield monkeypatch


def _graph(seed=5, log_n=11, factor=6):
    from sheep_tpu.utils.synth import rmat_edges
    n = 1 << log_n
    tail, head = rmat_edges(log_n, factor * n, seed=seed)
    return tail, head


def _ecv_down(seq, forest, tail, head, parts=4):
    from sheep_tpu.partition import Partition, evaluate_partition
    part = Partition.from_forest(seq, forest, num_parts=parts)
    rep = evaluate_partition(part.parts, tail, head, seq, num_parts=parts)
    return int(rep.ecv_down)


# ---------------------------------------------------------------------------
# bit-identical outputs for every thread count
# ---------------------------------------------------------------------------


@needs_omp
@pytest.mark.parametrize("blocked", ["1", "0"])
def test_build_bit_identical_across_thread_counts(thread_env, blocked):
    """T in {1,2,4,8} forced on this host: parent+pst CRCs and
    ECV(down) equal to the serial build for BOTH the bucket-run
    (blocked) and the per-slice (plain) decompositions."""
    thread_env.setenv("SHEEP_NATIVE_BLOCKED", blocked)
    tail, head = _graph()
    seq = degree_sequence(tail, head)
    want = build_forest(tail, head, seq, impl="native")
    ecv0 = _ecv_down(seq, want, tail, head)
    for t in (1, 2, 4, 8):
        thread_env.setenv("SHEEP_NATIVE_THREADS", str(t))
        got = build_forest(tail, head, seq, impl="native")
        np.testing.assert_array_equal(got.parent, want.parent)
        np.testing.assert_array_equal(got.pst_weight, want.pst_weight)
        assert _ecv_down(seq, got, tail, head) == ecv0


@needs_omp
def test_histograms_and_sorts_bit_identical(thread_env):
    """The histogram accumulator, the fused degree sequence, and the
    threaded counting sort all equal their serial outputs exactly."""
    tail, head = _graph(seed=9)
    n = int(max(tail.max(), head.max())) + 1
    want_deg = native.degree_histogram(tail, head, n)
    want_seq = native.degree_sequence_from_edges(tail, head, n)
    want_sort = native.degree_sequence_from_degrees(want_deg)
    for t in (2, 4, 8):
        thread_env.setenv("SHEEP_NATIVE_THREADS", str(t))
        np.testing.assert_array_equal(
            native.degree_histogram(tail, head, n), want_deg)
        acc = np.zeros(n, dtype=np.int64)
        native.degree_histogram_acc(tail, head, acc)
        native.degree_histogram_acc(tail, head, acc)
        np.testing.assert_array_equal(acc, 2 * want_deg)
        np.testing.assert_array_equal(
            native.degree_sequence_from_edges(tail, head, n), want_seq)
        np.testing.assert_array_equal(
            native.degree_sequence_from_degrees(want_deg), want_sort)


@needs_omp
def test_threaded_histogram_rejects_bad_vid(thread_env):
    thread_env.setenv("SHEEP_NATIVE_THREADS", "4")
    tail = np.array([0, 1, 99], dtype=np.uint32)
    head = np.array([1, 2, 3], dtype=np.uint32)
    with pytest.raises(ValueError, match="out of range"):
        native.degree_histogram(np.repeat(tail, 400),
                                np.repeat(head, 400), 50)


def _eval_graph():
    """(tail, head, parts, pos): a hub (vid 0 on a third of the records),
    self-loops and repeated records over 400 vids, parts over three
    64-part windows."""
    rng = np.random.default_rng(11)
    n, e = 400, 3000
    tail = rng.integers(0, n, e).astype(np.uint32)
    head = rng.integers(0, n, e).astype(np.uint32)
    tail[::3] = 0                                   # the hub
    head[::29] = tail[::29]                         # self-loops
    tail = np.concatenate([tail, tail[:500]])       # repeated records
    head = np.concatenate([head, head[:500]])
    parts = rng.integers(0, 3 * 64, n).astype(np.int64)
    return tail, head, parts, rng.permutation(n).astype(np.uint32)


def _eval_block(block, tail, head, parts, pos, w0, words):
    """One first-window block through ``block`` on fresh state; returns
    the cut and the state arrays it updated."""
    n, P = len(parts), int(parts.max()) + 1
    maps = [np.zeros(n * words, np.uint64) for _ in range(4)]
    if pos is None:
        maps[2:] = [None, None]
    state = maps + [np.zeros(n, np.uint8)] + \
        [np.zeros(P, np.int64) for _ in range(3)]
    cut = block(tail, head, parts, pos, w0, words, True, *state, P)
    return cut, [s for s in state if s is not None]


@needs_omp
@pytest.mark.parametrize("w0", [0, 64])
@pytest.mark.parametrize("words", [1, 2])
@pytest.mark.parametrize("with_seq", [True, False])
@pytest.mark.parametrize("threads", [1, 2, 4])
def test_eval_block_bit_identical_across_thread_counts(thread_env, threads,
                                                       with_seq, words, w0):
    """The bitmap scan's threaded arm ORs into shared bitmaps and sums
    per-thread loads: bitmaps, deg_mask, the three load arrays and the cut
    equal the serial kernel's and the numpy body's for every T."""
    from sheep_tpu.partition.evaluate import _eval_block_python
    tail, head, parts, pos = _eval_graph()
    args = (tail, head, parts, pos if with_seq else None, w0, words)
    want = _eval_block(_eval_block_python, *args)
    thread_env.setenv("SHEEP_NATIVE_THREADS", "1")
    serial = _eval_block(native.eval_block, *args)
    thread_env.setenv("SHEEP_NATIVE_THREADS", str(threads))
    assert native.threads_for(len(tail)) == threads
    got = _eval_block(native.eval_block, *args)
    for ref in (serial, want):
        assert got[0] == ref[0]
        assert len(got[1]) == len(ref[1])
        for a, b in zip(got[1], ref[1]):
            np.testing.assert_array_equal(a, b)


@needs_omp
@pytest.mark.parametrize("fault", ["vid", "part"])
def test_threaded_eval_block_rejects_bad_input(thread_env, fault):
    """An out-of-range vid, or a streamed vertex with INVALID_PART, makes
    one slice of the threaded scan fail, and the call raises."""
    from sheep_tpu import INVALID_PART
    thread_env.setenv("SHEEP_NATIVE_THREADS", "4")
    tail, head, parts, pos = _eval_graph()
    if fault == "vid":
        tail[len(tail) // 2] = len(parts)
    else:
        parts[tail[len(tail) // 2]] = INVALID_PART
    assert native.threads_for(len(tail)) == 4
    with pytest.raises(ValueError, match="out of range"):
        _eval_block(native.eval_block, tail, head, parts, pos, 0, 1)


@needs_omp
def test_resumable_fold_threaded_matches_pylinksfold(thread_env):
    """The windowed resumable fold under forced threads equals the
    python oracle window for window — the streaming handoff's and ext
    rung's exact contract."""
    tail, head = _graph(seed=3)
    seq = degree_sequence(tail, head)
    n = len(seq)
    lo, hi = edges_to_positions(tail, head, seq)
    linked = hi < n
    lo_t, hi_t = lo[linked], hi[linked]
    order = np.argsort(hi_t, kind="stable")
    lo_s, hi_s = lo_t[order], hi_t[order]
    oracle = PyLinksFold(n)
    oracle.block(lo, hi)
    want_p, want_w = oracle.finish()
    for t in (1, 4, 8):
        thread_env.setenv("SHEEP_NATIVE_THREADS", str(t))
        fold = native.LinksFold(n)
        cuts = np.linspace(0, len(lo_s), 4).astype(int)
        # pst-only links ride in the first window like the serial path
        fold.block(np.concatenate([lo[~linked], lo_s[:cuts[1]]]),
                   np.concatenate([hi[~linked], hi_s[:cuts[1]]]))
        fold.block(lo_s[cuts[1]:cuts[2]], hi_s[cuts[1]:cuts[2]])
        fold.block(lo_s[cuts[2]:], hi_s[cuts[2]:])
        p, w = fold.finish()
        np.testing.assert_array_equal(p, want_p)
        np.testing.assert_array_equal(w, want_w)


# ---------------------------------------------------------------------------
# partial-merge parity + bracket independence
# ---------------------------------------------------------------------------


@needs_omp
def test_partial_merge_parity_vs_python_oracle(thread_env):
    """Per-slice partial forests (what each worker thread builds) merge
    to the python oracle's whole-graph forest under ANY bracket: k-way
    concat, left-leaning pairwise, and balanced pairwise all agree —
    the bracket independence that lets a checkpoint resume under a
    different thread count."""
    tail, head = _graph(seed=13, log_n=9)
    seq = degree_sequence(tail, head)
    n = len(seq)
    m = len(tail)
    cuts = [0, m // 4, m // 2, 3 * m // 4, m]
    partials = [build_forest(tail[a:b], head[a:b], seq,
                             max_vid=int(max(tail.max(), head.max())),
                             impl="native")
                for a, b in zip(cuts[:-1], cuts[1:])]
    lo, hi = edges_to_positions(tail, head, seq)
    oracle = PyLinksFold(n)
    oracle.block(lo, hi)
    want_p, _ = oracle.finish()

    kway = merge_forests(*partials)
    left = merge_forests(
        merge_forests(merge_forests(partials[0], partials[1]),
                      partials[2]), partials[3])
    balanced = merge_forests(merge_forests(partials[0], partials[1]),
                             merge_forests(partials[2], partials[3]))
    for got in (kway, left, balanced):
        np.testing.assert_array_equal(got.parent, want_p)
        np.testing.assert_array_equal(got.pst_weight, kway.pst_weight)


@needs_omp
def test_checkpoint_resumes_under_different_thread_count(tmp_path,
                                                         thread_env):
    """The bracket-independence PROOF in action: a checkpoint written
    by a T=1 build resumes under forced T=4 (and vice versa) to the
    bit-identical forest — so the thread count does NOT belong in
    ``input_sig``; the partial-merge bracket is not part of the build's
    identity."""
    from sheep_tpu.io.edges import write_dat
    from sheep_tpu.ops.extmem import build_forest_extmem
    from sheep_tpu.runtime import (BuildKilled, FaultPlan, clear_plan,
                                   install_plan, reset_counters)
    tail, head = _graph(seed=7, log_n=10)
    path = str(tmp_path / "g.dat")
    write_dat(path, tail, head)
    seq0 = degree_sequence(tail, head)
    want = build_forest(tail, head, seq0)
    B = 900
    for t_first, t_second in (("1", "4"), ("4", "1")):
        ck = str(tmp_path / f"ck-{t_first}-{t_second}")
        thread_env.setenv("SHEEP_NATIVE_THREADS", t_first)
        reset_counters()
        install_plan(FaultPlan(site="ext-boundary", at=2, kind="kill"))
        with pytest.raises(BuildKilled):
            build_forest_extmem(path, block_edges=B, checkpoint_dir=ck)
        clear_plan()
        reset_counters()
        thread_env.setenv("SHEEP_NATIVE_THREADS", t_second)
        seq, f = build_forest_extmem(path, block_edges=B,
                                     checkpoint_dir=ck, resume=True)
        np.testing.assert_array_equal(seq, seq0)
        np.testing.assert_array_equal(f.parent, want.parent)
        np.testing.assert_array_equal(f.pst_weight, want.pst_weight)


@needs_omp
def test_kill_during_threaded_fold_resume_sweep(tmp_path, thread_env):
    """Kill a FORCED-threads ext build at every block boundary; the
    threaded resume is bit-identical with equal ECV(down)."""
    from sheep_tpu.io.edges import write_dat
    from sheep_tpu.ops.extmem import build_forest_extmem
    from sheep_tpu.runtime import (BuildKilled, FaultPlan, clear_plan,
                                   install_plan, reset_counters)
    tail, head = _graph(seed=21, log_n=10)
    path = str(tmp_path / "g.dat")
    write_dat(path, tail, head)
    seq0 = degree_sequence(tail, head)
    want = build_forest(tail, head, seq0)
    ecv0 = _ecv_down(seq0, want, tail, head)
    thread_env.setenv("SHEEP_NATIVE_THREADS", "4")
    B = 1600
    nblocks = -(-len(tail) // B)
    for k in range(nblocks):
        ck = str(tmp_path / f"ck{k}")
        reset_counters()
        install_plan(FaultPlan(site="ext-boundary", at=k, kind="kill"))
        with pytest.raises(BuildKilled):
            build_forest_extmem(path, block_edges=B, checkpoint_dir=ck)
        clear_plan()
        reset_counters()
        seq, f = build_forest_extmem(path, block_edges=B,
                                     checkpoint_dir=ck, resume=True)
        np.testing.assert_array_equal(seq, seq0)
        np.testing.assert_array_equal(f.parent, want.parent)
        np.testing.assert_array_equal(f.pst_weight, want.pst_weight)
        assert _ecv_down(seq, f, tail, head) == ecv0


# ---------------------------------------------------------------------------
# resolution: affinity clamp, governor plan, quota detection
# ---------------------------------------------------------------------------


@needs_omp
def test_forced_threads_clamp_to_granted_cores(thread_env):
    """Without the explicit oversubscription opt-in, a forced count
    clamps to the granted cores — spinning compute threads on a core
    they time-share is never what an operator wants."""
    thread_env.delenv("SHEEP_NATIVE_OVERSUB", raising=False)
    thread_env.setenv("SHEEP_NATIVE_THREADS", "64")
    cores = len(os.sched_getaffinity(0))
    assert native.resolve_threads() == min(64, cores)
    thread_env.setenv("SHEEP_NATIVE_OVERSUB", "1")
    assert native.resolve_threads() == 64


def test_threads_report_one_without_config():
    assert native.resolve_threads() >= 1
    assert native.threads_for(10) >= 1
    assert native.omp_max_threads() >= 1


def test_governor_thread_plan(thread_env, monkeypatch):
    from sheep_tpu.resources.governor import (ResourceGovernor,
                                              native_thread_plan)
    import sheep_tpu.utils.envinfo as envinfo
    monkeypatch.setattr(envinfo, "effective_cores", lambda root=None: 8)
    n = 1 << 20
    # unbudgeted: all effective cores
    plan = native_thread_plan(n, ResourceGovernor())
    assert plan["threads"] == 8 and not plan["forced"]
    # SHEEP_LEG_CORES caps it (a distext leg must not oversubscribe)
    thread_env.setenv("SHEEP_LEG_CORES", "2")
    plan = native_thread_plan(n, ResourceGovernor())
    assert plan["threads"] == 2
    assert "leg cores" in plan["reason"]
    thread_env.delenv("SHEEP_LEG_CORES")
    # a tight memory budget vetoes threads: 8n per extra thread
    gov = ResourceGovernor(mem_budget=1)  # headroom already negative
    plan = native_thread_plan(n, gov)
    assert plan["threads"] == 1
    assert "vetoed" in plan["reason"]
    # the operator pin is never second-guessed by the plan
    thread_env.setenv("SHEEP_NATIVE_THREADS", "4")
    plan = native_thread_plan(n, gov)
    assert plan["threads"] == 4 and plan["forced"]


def test_rung_pricing_includes_thread_tables():
    from sheep_tpu.resources.governor import (native_thread_tables_nbytes,
                                              rung_peak_nbytes)
    n, links = 1 << 20, 1 << 22
    assert native_thread_tables_nbytes(n, 1) == 0
    assert native_thread_tables_nbytes(n, 4) == 8 * n * 3
    for rung in ("host", "stream", "ext", "spill"):
        base = rung_peak_nbytes(rung, n, links)
        assert rung_peak_nbytes(rung, n, links, threads=4) \
            == base + 8 * n * 3
    # device rungs never run the native fold: no thread term
    assert rung_peak_nbytes("single", n, links, threads=4) \
        == rung_peak_nbytes("single", n, links)


def test_cpu_quota_detection(tmp_path):
    from sheep_tpu.utils.envinfo import cpu_quota_cores, effective_cores
    # cgroup v2
    v2 = tmp_path / "v2"
    v2.mkdir()
    (v2 / "cpu.max").write_text("400000 100000\n")
    assert cpu_quota_cores(str(v2)) == 4.0
    (v2 / "cpu.max").write_text("max 100000\n")
    assert cpu_quota_cores(str(v2)) is None
    # cgroup v1
    v1 = tmp_path / "v1"
    (v1 / "cpu").mkdir(parents=True)
    (v1 / "cpu" / "cpu.cfs_quota_us").write_text("150000\n")
    (v1 / "cpu" / "cpu.cfs_period_us").write_text("100000\n")
    assert cpu_quota_cores(str(v1)) == 1.5
    (v1 / "cpu" / "cpu.cfs_quota_us").write_text("-1\n")
    assert cpu_quota_cores(str(v1)) is None
    # effective cores: min(affinity, ceil(quota)), floor 1
    (v2 / "cpu.max").write_text("50000 100000\n")  # half a core
    assert effective_cores(str(v2)) == 1
    assert effective_cores(str(tmp_path / "nope")) >= 1


def test_env_capture_reports_quota_and_omp():
    from sheep_tpu.utils.envinfo import env_capture
    rec = env_capture()
    assert "effective_cores" in rec
    # native is loaded by this test module, so the OpenMP fields appear
    assert "omp_compiled" in rec
    assert rec["omp_max_threads"] >= 1


# ---------------------------------------------------------------------------
# observability: span threads field + ladder.plan explanation
# ---------------------------------------------------------------------------


@needs_omp
def test_native_spans_carry_threads_field(tmp_path, thread_env):
    from sheep_tpu.obs import trace as obs_trace
    tail, head = _graph(seed=2)
    seq = degree_sequence(tail, head)
    thread_env.setenv("SHEEP_NATIVE_THREADS", "4")
    tpath = str(tmp_path / "x.trace")
    thread_env.setenv(obs_trace.ENV, tpath)
    try:
        build_forest(tail, head, seq, impl="native")
    finally:
        obs_trace.close_recorder()
    records, _, _ = obs_trace.read_trace(tpath, "strict")
    spans = [r.get("a", {}) for r in records if r.get("k") == "span"
             and r.get("name", "").startswith("native.")]
    assert spans, records
    threaded = [a for a in spans if a.get("threads") == 4]
    assert threaded, spans
    assert any(len(a.get("thread_busy_s", [])) == 4 for a in threaded)


def test_ladder_plan_event_explains_thread_choice(tmp_path, monkeypatch):
    from sheep_tpu.obs import trace as obs_trace
    from sheep_tpu.runtime import RuntimeConfig, build_graph_resilient
    monkeypatch.delenv("SHEEP_NATIVE_THREADS", raising=False)
    tail, head = _graph(seed=4, log_n=8)
    tpath = str(tmp_path / "plan.trace")
    monkeypatch.setenv(obs_trace.ENV, tpath)
    try:
        cfg = RuntimeConfig(ladder=("host",))
        build_graph_resilient(tail, head, config=cfg)
    finally:
        obs_trace.close_recorder()
    records, _, _ = obs_trace.read_trace(tpath, "strict")
    plans = [r for r in records if r.get("name") == "ladder.plan"]
    assert plans, records
    nt = plans[0].get("a", {}).get("native_threads")
    assert nt and nt["threads"] >= 1 and "reason" in nt
    assert any(e[0] == "native-threads" for e in cfg.events)
