"""The readers of the program's own spans, on hand-made records: each
gives the expected value per call, and None where its spans are absent
(a program that lacks them, as the parent of the change that added
them does)."""

import pytest

from conftest import load_run


def _span(name, sid, par, t, dur, cpu=None, tid=1):
    return {"k": "span", "name": name, "id": sid, "par": par, "tid": tid,
            "t": t, "dur": dur, "cpu": dur if cpu is None else cpu, "a": {}}


META = {"k": "meta", "v": 1, "pid": 1, "t0": 0.0, "argv": []}


def _tree_call(scale=1.0):
    """A graph2tree call: root 10 s; children cover [0.5, 9.5] but for
    the gap [3, 4], and a bound thread's span overlaps the loop."""
    s = scale
    return [META,
            _span("io.load", 2, 1, 0.5, 1.0 * s),
            _span("prep", 3, 1, 1.5, 1.5 * s, cpu=0.1),
            _span("prep.host", 4, 1, 1.5, 1.0, tid=2),
            _span("reduce.loop", 5, 1, 1.5, 1.5),
            _span("reduce.chunk.inner", 6, 5, 1.6, 0.2),
            _span("handoff.fetch", 7, 1, 4.0, 3.0),
            _span("io.write", 8, 1, 7.0, 1.0 * s),
            _span("io.write", 9, 1, 8.0, 1.5 * s),
            {"k": "ev", "name": "hybrid.handoff", "par": 1, "tid": 1,
             "t": 7.0, "a": {"rounds": 4}},
            _span("cli.graph2tree", 1, None, 0.0, 10.0)]


def _partition_call():
    return [META,
            _span("io.read", 2, 1, 0.1, 0.4),
            _span("io.load", 3, 1, 0.5, 1.0),
            _span("partition.tree", 4, 1, 1.5, 0.5),
            _span("evaluate.gather", 6, 5, 2.0, 4.0),
            _span("evaluate.vcom", 7, 5, 6.0, 6.0),
            _span("evaluate.ecv_hash", 8, 5, 12.0, 16.0),
            _span("evaluate.ecv_seq", 9, 5, 28.0, 13.0),
            _span("partition.evaluate", 5, 1, 2.0, 40.0, cpu=36.5),
            _span("cli.partition_tree", 1, None, 0.0, 42.5)]


@pytest.fixture(scope="module")
def run(tiny_root):
    return load_run(tiny_root)


def _obs(run, tree=(), partition=()):
    return run.Observed(list(tree), list(partition), None, None)


@pytest.mark.parametrize("metric,want", [
    # two calls, the second's load and writes twice as long
    ("io.load_s", (1.0 + 2.0) / 2),
    ("io.write_s", (2.5 + 5.0) / 2),
    ("prep.ready_s", (1.5 + 3.0) / 2),
    # 0 to 0.5, 3 to 4 and 9.5 to 10 in the first call; in the second,
    # the longer children leave 0 to 0.5 alone uncovered
    ("untraced.tree_s", (2.0 + 0.5) / 2),
])
def test_tree_readers(run, metric, want):
    got = run.load_reader(metric)(_obs(run, tree=[_tree_call(),
                                                  _tree_call(2.0)]))
    assert got == pytest.approx(want)


@pytest.mark.parametrize("metric,want", [
    ("evaluate.wall_s", 40.0),
    ("evaluate.wait_s", 3.5),
    ("untraced.partition_s", 0.1 + 0.5),
])
def test_partition_readers(run, metric, want):
    got = run.load_reader(metric)(_obs(run, partition=[_partition_call()]))
    assert got == pytest.approx(want)


@pytest.mark.parametrize("metric", [
    "io.load_s", "io.write_s", "prep.ready_s", "untraced.tree_s",
    "evaluate.wall_s", "evaluate.wait_s", "untraced.partition_s"])
def test_silent_without_their_spans(run, metric):
    """The parent's records: the old spans, no root, no new layer."""
    old = [META, _span("prep.device", 2, None, 0.0, 0.01),
           _span("reduce.loop", 3, None, 0.1, 7.0),
           _span("native.forward_partition", 4, None, 0.0, 0.02)]
    read = run.load_reader(metric)
    assert read(_obs(run, tree=[old], partition=[old])) is None
    assert read(_obs(run)) is None


def test_a_thread_span_explains_no_gap_on_the_root_thread(run):
    """A bound thread's span over the root thread's gap [3, 4] runs beside
    that thread's work: the gap stays unexplained."""
    call = _tree_call()
    call.insert(-1, _span("fetch.slice", 10, 1, 2.9, 1.2, tid=2))
    got = run.load_reader("untraced.tree_s")(_obs(run, tree=[call]))
    assert got == pytest.approx(2.0)


def test_wait_is_never_below_zero(run):
    """CPU time may read a clock tick above the wall time: no wait."""
    call = [META, _span("partition.evaluate", 2, None, 0.0, 1.0, cpu=1.0001)]
    got = run.load_reader("evaluate.wait_s")(_obs(run, partition=[call]))
    assert got == 0.0

