"""The ``evaluate.scan_threads`` reader on hand-made records: the threads
the part-set bitmap scan ran on per partition_tree call, and None where
the ``evaluate.scan`` span carries no ``threads`` attribute (a program
whose scan is serial and says nothing of it) or is absent."""

import pytest

from conftest import load_run


def _span(name, sid, par, t, dur, cpu=None, tid=1):
    return {"k": "span", "name": name, "id": sid, "par": par, "tid": tid,
            "t": t, "dur": dur, "cpu": dur if cpu is None else cpu, "a": {}}


META = {"k": "meta", "v": 1, "pid": 1, "t0": 0.0, "argv": []}


def _scan_call(attrs):
    """A partition_tree call whose bitmap scan span carries ``attrs``."""
    scan = _span("evaluate.scan", 6, 5, 2.0, 3.0)
    scan["a"] = attrs
    return [META, scan, _span("partition.evaluate", 5, 1, 2.0, 3.1),
            _span("cli.partition_tree", 1, None, 0.0, 5.0)]


@pytest.fixture(scope="module")
def run(tiny_root):
    return load_run(tiny_root)


def _obs(run, partition=()):
    return run.Observed([], list(partition), None, None)


@pytest.mark.parametrize("attrs,want", [
    # the kernel's own count, the mean over the calls
    ([{"windows": 1, "threads": 13, "thread_busy_s": [0.1] * 13},
      {"windows": 1, "threads": 9}], 11.0),
    ([{"windows": 1, "threads": 1}], 1.0),
    # the parent's scan span carries no count: silent
    ([{"windows": 1, "passes": 1, "source": "memory"}], None),
])
def test_scan_threads_reader(run, attrs, want):
    got = run.load_reader("evaluate.scan_threads")(
        _obs(run, partition=[_scan_call(a) for a in attrs]))
    assert got == (None if want is None else pytest.approx(want))


def test_scan_threads_silent_without_the_scan_span(run):
    """Records with no ``evaluate.scan`` span at all, and no records."""
    old = [META, _span("prep.device", 2, None, 0.0, 0.01),
           _span("reduce.loop", 3, None, 0.1, 7.0),
           _span("native.forward_partition", 4, None, 0.0, 0.02)]
    read = run.load_reader("evaluate.scan_threads")
    assert read(_obs(run, partition=[old])) is None
    assert read(_obs(run)) is None
