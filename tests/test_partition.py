"""Partitioner + evaluator: goldens on hep-th and brute-force parity.

Golden values from the reference's published log data/quality/hep.degree.raw
(degree sequence, balance 1.03, pst weights — the partition_tree defaults).
"""

import numpy as np
import pytest

from sheep_tpu import INVALID_PART
from sheep_tpu.core import build_forest, degree_sequence
from sheep_tpu.core.sequence import sequence_positions
from sheep_tpu.partition import (
    Partition,
    TreePartitionOptions,
    evaluate_partition,
    partition_forest,
)
from sheep_tpu.partition.evaluate import cormen_hash
from conftest import random_multigraph

GOLDEN = {
    2: dict(sizes=(3409, 4201), edges_cut=2811, vcom=2061, ecv_hash=1311,
            ecv_down=521, ecv_up=1539),
    3: dict(sizes=(2323, 2205), edges_cut=3973, vcom=3256, ecv_hash=2042,
            ecv_down=888, ecv_up=2364),
    4: dict(sizes=(1662, 1714), edges_cut=4601, vcom=4075, ecv_hash=2452,
            ecv_down=1177, ecv_up=2893),
}


@pytest.fixture(scope="module")
def hep_setup(hep_edges):
    seq = degree_sequence(hep_edges.tail, hep_edges.head)
    forest = build_forest(hep_edges.tail, hep_edges.head, seq)
    return hep_edges, seq, forest


@pytest.mark.parametrize("nparts", [2, 3, 4])
def test_hepth_partition_goldens(hep_setup, nparts):
    el, seq, forest = hep_setup
    p = Partition.from_forest(seq, forest, nparts, max_vid=el.max_vid)
    g = GOLDEN[nparts]
    assert p.max_part + 1 == nparts
    first = int((p.parts == 0).sum())
    second = int((p.parts == 1).sum())
    assert (first, second) == g["sizes"]

    rep = evaluate_partition(p.parts, el.tail, el.head, seq, nparts,
                             max_vid=el.max_vid, file_edges=el.file_edges)
    assert rep.edges_cut == g["edges_cut"]
    assert rep.vcom_vol == g["vcom"]
    assert rep.ecv_hash == g["ecv_hash"]
    assert rep.ecv_down == g["ecv_down"]
    assert rep.ecv_up == g["ecv_up"]


def brute_force_eval(parts, tail, head, seq, num_parts, file_edges):
    """Literal replay of lib/partition.cpp:428-521 with python sets."""
    pos = {int(v): i for i, v in enumerate(seq)}
    adj = {}
    for t, h in zip(tail.tolist(), head.tolist()):
        adj.setdefault(t, []).append(h)
        adj.setdefault(h, []).append(t)

    edges_cut = vcom = ecv_hash = ecv_down = ecv_up = 0
    P = int(max(parts)) + 1
    vert_bal = [0] * P
    hash_bal = [0] * P
    down_bal = [0] * P
    up_bal = [0] * P

    ch = lambda k: int(cormen_hash(np.array([k], dtype=np.uint32))[0])
    for X in sorted(adj):
        Xp = int(parts[X])
        vert_bal[Xp] += 1
        vset = {Xp}
        hset = set()
        dset = set()
        uset = set()
        for Y in adj[X]:
            Yp = int(parts[Y])
            if X < Y and Xp != Yp:
                edges_cut += 1
            vset.add(Yp)
            hp = Xp if ch(X) < ch(Y) else Yp
            hset.add(hp)
            if X < Y:
                hash_bal[hp] += 1
            dset.add(Xp if pos[X] < pos[Y] else Yp)
            uset.add(Xp if pos[X] > pos[Y] else Yp)
            if pos[X] < pos[Y]:
                down_bal[Xp] += 1
            if pos[X] > pos[Y]:
                up_bal[Xp] += 1
        vcom += len(vset) - 1
        ecv_hash += len(hset) - 1
        ecv_down += len(dset) - 1
        ecv_up += len(uset) - 1
    return dict(edges_cut=edges_cut, vcom=vcom, ecv_hash=ecv_hash,
                ecv_down=ecv_down, ecv_up=ecv_up,
                vertex_balance=max(vert_bal), hash_balance=max(hash_bal),
                down_balance=max(down_bal), up_balance=max(up_bal))


@pytest.mark.parametrize("seed", range(12))
def test_evaluator_matches_bruteforce(seed):
    rng = np.random.default_rng(300 + seed)
    tail, head = random_multigraph(rng, n_max=30, e_max=90)
    seq = degree_sequence(tail, head)
    n = int(max(tail.max(), head.max())) + 1
    parts = np.full(n, INVALID_PART, dtype=np.int64)
    parts[seq] = rng.integers(0, 3, size=len(seq))

    rep = evaluate_partition(parts, tail, head, seq, 3)
    bf = brute_force_eval(parts, tail, head, seq, 3, len(tail))
    assert rep.edges_cut == bf["edges_cut"]
    assert rep.vcom_vol == bf["vcom"]
    assert rep.ecv_hash == bf["ecv_hash"]
    assert rep.ecv_down == bf["ecv_down"]
    assert rep.ecv_up == bf["ecv_up"]
    assert rep.vertex_balance == bf["vertex_balance"]
    assert rep.hash_balance == bf["hash_balance"]
    assert rep.down_balance == bf["down_balance"]
    assert rep.up_balance == bf["up_balance"]


@pytest.mark.parametrize("strategy", ["forward", "backward", "depth", "height", "naive"])
@pytest.mark.parametrize("seed", range(4))
def test_strategies_assign_everything(strategy, seed):
    rng = np.random.default_rng(400 + seed)
    tail, head = random_multigraph(rng, n_max=50, e_max=200)
    seq = degree_sequence(tail, head)
    forest = build_forest(tail, head, seq)
    jparts = partition_forest(forest, 3, strategy=strategy)
    assert (jparts != INVALID_PART).all()
    assert jparts.min() >= 0


@pytest.mark.parametrize("seed", range(6))
def test_forward_balance_invariant(seed):
    """forwardPartition respects max_component per bin (partition.cpp:114,133)."""
    rng = np.random.default_rng(500 + seed)
    tail, head = random_multigraph(rng, n_max=60, e_max=400)
    seq = degree_sequence(tail, head)
    forest = build_forest(tail, head, seq)
    opts = TreePartitionOptions()
    jparts = partition_forest(forest, 4, opts)
    w = forest.pst_weight.astype(np.int64)
    total = int(w.sum())
    max_component = int((total // 4) * opts.balance_factor)
    loads = np.bincount(jparts, weights=w)
    # The algorithm guarantees every bin stays within max_component.
    assert (loads <= max_component).all()


def test_partition_writers(tmp_path, hep_setup):
    el, seq, forest = hep_setup
    p = Partition.from_forest(seq, forest, 2, max_vid=el.max_vid)
    prefix = str(tmp_path / "out-p")
    paths = p.write_partitioned_graph(el.tail, el.head, seq, prefix,
                                      max_vid=el.max_vid)
    assert len(paths) == 2
    # downward assignment: every non-loop edge lands in exactly one file
    import os
    tot = 0
    for path in paths:
        with open(path) as f:
            tot += sum(1 for _ in f)
    n_loops = int((el.tail == el.head).sum())
    assert tot == el.num_edges - n_loops

    iso = str(tmp_path / "iso.net")
    p.write_isomorphic_graph(el.tail, el.head, seq, iso, max_vid=el.max_vid)
    assert os.path.getsize(iso) > 0


def test_forward_overweight_node_raises():
    """A node heavier than max_component must fail fast, not hang
    (the reference's live assert at partition.cpp:114)."""
    tail = np.array([0, 1], dtype=np.uint32)
    head = np.array([1, 2], dtype=np.uint32)
    seq = degree_sequence(tail, head)
    forest = build_forest(tail, head, seq)
    with pytest.raises(ValueError, match="max_component"):
        partition_forest(forest, 8)


def test_balance_denominators_truncate(capsys):
    """Printed balance fractions use integer-divided denominators
    (partition.cpp:470: max_bal / (getNodes() / num_parts))."""
    from sheep_tpu.partition.evaluate import EvalReport
    rep = EvalReport(edges_cut=0, vcom_vol=0, ecv_hash=0, ecv_down=0,
                     ecv_up=0, vertex_balance=5, hash_balance=0,
                     down_balance=0, up_balance=0,
                     num_edges=10, num_nodes=9, num_parts=2)
    rep.print()
    out = capsys.readouterr().out
    assert "balance: 5 (1.250000%)" in out  # 5 / (9 // 2), not 5 / 4.5


@pytest.mark.parametrize("impl", ["native", "python"])
@pytest.mark.parametrize("num_parts", [2, 7, 100])
def test_streamed_evaluator_matches_inmemory(num_parts, impl):
    # The O(n)-memory bitmap evaluator must be bit-identical to the dense
    # one, including the >64-part multi-window path (num_parts=100) —
    # through BOTH the native per-block kernel (sheep_eval_block) and the
    # pure-numpy fallback body.  impl="native" raises if the runtime
    # failed to build, so a broken .so can't silently skip the C coverage.
    from sheep_tpu.core.sequence import degree_sequence, sequence_positions
    from sheep_tpu.partition.evaluate import (evaluate_partition,
                                              evaluate_partition_streamed)

    rng = np.random.default_rng(42 + num_parts)
    n = 300
    e = 1500
    tail = rng.integers(0, n, e).astype(np.uint32)
    head = rng.integers(0, n, e).astype(np.uint32)
    seq = degree_sequence(tail, head)
    parts = rng.integers(0, num_parts, n).astype(np.int64)

    dense = evaluate_partition(parts, tail, head, seq, num_parts,
                               max_vid=n - 1, file_edges=e, impl="python")
    pos = sequence_positions(seq, n - 1).astype(np.int64)

    def blocks():
        for a in range(0, e, 64):
            yield tail[a:a + 64], head[a:a + 64]

    stream = evaluate_partition_streamed(parts, blocks, pos, num_parts, e,
                                         impl=impl)
    assert dense == stream

    # sequence-free overload
    dense_nf = evaluate_partition(parts, tail, head, None, num_parts,
                                  max_vid=n - 1, file_edges=e, impl="python")
    stream_nf = evaluate_partition_streamed(parts, blocks, None, num_parts, e,
                                            impl=impl)
    assert dense_nf == stream_nf


def _oracle_graph(kind, num_parts):
    """(parts, tail, head, seq, max_vid, file_edges).  "mixed": self-loops,
    repeated records, vids without edges (INVALID_PART), and a file count
    above the loaded records, as a partial load has.  "sparse": few
    records over many vids, so the bitmap budget forces several passes."""
    from sheep_tpu import INVALID_PART
    from sheep_tpu.core.sequence import degree_sequence

    rng = np.random.default_rng(7 * num_parts + len(kind))
    n, e, used = {"mixed": (300, 1600, 250), "sparse": (5000, 600, 5000)}[kind]
    tail = rng.integers(0, used, e).astype(np.uint32)
    head = rng.integers(0, used, e).astype(np.uint32)
    file_edges = e
    if kind == "mixed":
        head[::17] = tail[::17]                     # self-loops
        tail = np.concatenate([tail, tail[:200]])   # repeated records
        head = np.concatenate([head, head[:200]])
        file_edges = len(tail) + 9
    seq = degree_sequence(tail, head)
    parts = np.full(n, INVALID_PART, dtype=np.int64)
    parts[seq] = rng.integers(0, num_parts, len(seq))
    parts[seq[-1]] = num_parts - 1                  # the highest part is used
    return parts, tail, head, seq, n - 1, file_edges


@pytest.mark.parametrize("kind", ["mixed", "sparse"])
@pytest.mark.parametrize("with_seq", [True, False])
@pytest.mark.parametrize("num_parts", [1, 2, 16, 64, 65, 100, 1024])
def test_bitmap_evaluator_matches_sort_oracle(num_parts, with_seq, kind,
                                              capsys):
    # The native part-set bitmap path over the loaded arrays must give the
    # sort body's report, field for field and in print, for every part
    # count: one window, several windows in one pass, and several passes.
    from sheep_tpu.partition.evaluate import _pass_words, evaluate_partition

    parts, tail, head, seq, max_vid, file_edges = _oracle_graph(kind,
                                                                num_parts)
    seq = seq if with_seq else None
    got, want = (evaluate_partition(parts, tail, head, seq, num_parts,
                                    max_vid=max_vid, file_edges=file_edges,
                                    impl=impl)
                 for impl in ("native", "python"))
    assert got == want
    printed = []
    for rep in (got, want):
        rep.print(with_seq=with_seq)
        printed.append(capsys.readouterr().out)
    assert printed[0] == printed[1] and "ECV(hash)" in printed[0]

    words, passes = _pass_words(len(parts), len(tail), num_parts,
                                4 if with_seq else 2)
    assert words * passes * 64 >= num_parts
    if kind == "sparse" and num_parts > 64:
        assert passes > 1


@pytest.mark.parametrize("fault", ["short", "no_down"])
def test_eval_block_refuses_bitmaps_of_the_wrong_size(fault):
    """The kernel indexes vid*words + word: a bitmap sized for fewer
    words, or a missing ECV(down) bitmap beside a position table, is
    refused before any pointer reaches it."""
    from sheep_tpu import native
    if not native.available():
        pytest.skip("native runtime not built")
    n, words = 8, 2
    tail = np.array([0, 1], np.uint32)
    head = np.array([2, 3], np.uint32)
    parts = np.zeros(n, np.int64)
    pos = np.arange(n, dtype=np.uint32)
    maps = [np.zeros(n * words, np.uint64) for _ in range(4)]
    if fault == "short":
        maps[1] = np.zeros(n, np.uint64)
    else:
        maps[2] = None
    loads = [np.zeros(1, np.int64) for _ in range(3)]
    with pytest.raises(ValueError, match="bitmaps"):
        native.eval_block(tail, head, parts, pos, 0, words, True, *maps,
                          np.zeros(n, np.uint8), *loads, 1)


def test_bitmap_budget_at_graph500_scale_21():
    """The four bitmaps of a pass fit in the sort body's doubled key
    array: one pass up to 512 parts at 2^21 vids and 2^25 records, two at
    1024; the sequence-free pair of bitmaps takes twice the windows."""
    from sheep_tpu.partition.evaluate import _pass_words

    n, e = 1 << 21, 1 << 25
    assert _pass_words(n, e, 16, 4) == (1, 1)
    assert _pass_words(n, e, 512, 4) == (8, 1)
    assert _pass_words(n, e, 1024, 4) == (8, 2)
    assert _pass_words(n, e, 1000, 4) == (8, 2)
    assert _pass_words(n, e, 1024, 2) == (16, 1)
    assert _pass_words(n, 16, 1024, 4) == (1, 16)


@pytest.fixture
def scan_threads(monkeypatch):
    """The native runtime with OpenMP, the threaded arm engaged on
    test-sized inputs and allowed past this host's cores, and a host of
    four effective cores for the thread plan."""
    from sheep_tpu import native
    import sheep_tpu.utils.envinfo as envinfo
    if not (native.available() and native.omp_compiled()):
        pytest.skip("native runtime with OpenMP not built")
    monkeypatch.setenv("SHEEP_NATIVE_THREAD_FLOOR", "0")
    monkeypatch.setenv("SHEEP_NATIVE_OVERSUB", "1")
    for k in ("SHEEP_NATIVE_THREADS", "SHEEP_MEM_BUDGET", "SHEEP_LEG_CORES"):
        monkeypatch.delenv(k, raising=False)
    monkeypatch.setattr(envinfo, "effective_cores", lambda root=None: 4)
    return monkeypatch


def test_evaluator_reports_equal_at_one_and_four_threads(scan_threads):
    parts, tail, head, seq, max_vid, file_edges = _oracle_graph("mixed", 16)
    reps = []
    for threads in ("1", "4"):
        scan_threads.setenv("SHEEP_NATIVE_THREADS", threads)
        reps.append(evaluate_partition(parts, tail, head, seq, 16,
                                       max_vid=max_vid, file_edges=file_edges,
                                       impl="native"))
    assert reps[0] == reps[1]


@pytest.mark.parametrize("pin", [None, "2"])
def test_evaluator_scan_runs_on_the_thread_plan(scan_threads, tmp_path, pin):
    """Unpinned, the scan runs on the plan's four threads and leaves
    SHEEP_NATIVE_THREADS unset; an operator's pin wins and stays set.
    The ``evaluate.scan`` span says what the kernel used."""
    import os
    from sheep_tpu.obs import trace as obs_trace
    if pin is not None:
        scan_threads.setenv("SHEEP_NATIVE_THREADS", pin)
    parts, tail, head, seq, max_vid, file_edges = _oracle_graph("mixed", 16)
    tpath = str(tmp_path / "eval.trace")
    scan_threads.setenv(obs_trace.ENV, tpath)
    try:
        evaluate_partition(parts, tail, head, seq, 16, max_vid=max_vid,
                           file_edges=file_edges, impl="native")
    finally:
        obs_trace.close_recorder()
    assert os.environ.get("SHEEP_NATIVE_THREADS") == pin
    records, _, _ = obs_trace.read_trace(tpath, "strict")
    (scan,) = [r["a"] for r in records if r.get("k") == "span"
               and r.get("name") == "evaluate.scan"]
    want = 4 if pin is None else int(pin)
    assert scan["threads"] == want
    assert len(scan["thread_busy_s"]) == want
