"""The ``evaluate.scan_s`` reader on hand-made records: the seconds of the
part-set bitmap scan per partition_tree call, and None on a call whose
evaluator ran the sort body (or on a program that has no such span)."""

import pytest

from conftest import load_run


def _span(name, sid, par, t, dur, cpu=None, tid=1):
    return {"k": "span", "name": name, "id": sid, "par": par, "tid": tid,
            "t": t, "dur": dur, "cpu": dur if cpu is None else cpu, "a": {}}


META = {"k": "meta", "v": 1, "pid": 1, "t0": 0.0, "argv": []}


def _sort_call():
    """A partition_tree call whose evaluator ran the four sort steps."""
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


def _bitmap_call(scan=3.0):
    """A partition_tree call whose evaluator ran the part-set bitmap
    kernel: a scan and a finish under ``partition.evaluate``."""
    return [META,
            _span("io.read", 2, 1, 0.1, 0.4),
            _span("io.load", 3, 1, 0.5, 1.0),
            _span("partition.tree", 4, 1, 1.5, 0.5),
            _span("evaluate.scan", 6, 5, 2.0, scan),
            _span("evaluate.finish", 7, 5, 2.0 + scan, 0.01),
            _span("partition.evaluate", 5, 1, 2.0, scan + 0.1, cpu=scan),
            _span("cli.partition_tree", 1, None, 0.0, scan + 2.2)]


@pytest.fixture(scope="module")
def run(tiny_root):
    return load_run(tiny_root)


def _obs(run, partition=()):
    return run.Observed([], list(partition), None, None)


@pytest.mark.parametrize("calls,want", [
    ("bitmap", 3.0),
    # the sort body has no scan: silent
    ("sort", None),
    # the mean over the calls that scanned
    ("bitmap,sort,bitmap2", (3.0 + 2.0) / 2),
])
def test_scan_reader_on_bitmap_and_sort_calls(run, calls, want):
    made = {"bitmap": _bitmap_call, "sort": _sort_call,
            "bitmap2": lambda: _bitmap_call(2.0)}
    got = run.load_reader("evaluate.scan_s")(
        _obs(run, partition=[made[c]() for c in calls.split(",")]))
    assert got == (None if want is None else pytest.approx(want))


def test_scan_reader_silent_without_its_spans(run):
    """The parent's records: the old spans, no root, no scan."""
    old = [META, _span("prep.device", 2, None, 0.0, 0.01),
           _span("reduce.loop", 3, None, 0.1, 7.0),
           _span("native.forward_partition", 4, None, 0.0, 0.02)]
    read = run.load_reader("evaluate.scan_s")
    assert read(_obs(run, partition=[old])) is None
    assert read(_obs(run)) is None
