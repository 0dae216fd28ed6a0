"""Unit tests for bench.py's sweep loop fault semantics.

A wrong continue/stop decision silently costs a sweep's sizes (round-4
lesson: a sweep died at 2^16 because a timeout that had only cut the
secondary path was treated as a sweep-ending fault).  run_child is
injected, so no jax and no subprocesses here, except in the one test of
the no-chip refusal.
"""

import importlib.util
import json
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def bench():
    spec = importlib.util.spec_from_file_location(
        "bench", os.path.join(REPO, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _rec(log_n, eps=1000.0):
    return json.dumps({"log_n": log_n, "edges_per_sec": eps,
                       "rounds": 0, "best_s": 1.0})


def test_clean_sweep(bench):
    child = lambda log_n: (_rec(log_n), "", 0, None)
    sweep, fault = bench.run_sweep([16, 18], child, 100)
    assert fault is None
    assert [r["log_n"] for r in sweep] == [16, 18]
    assert not any(r.get("partial") for r in sweep)


def test_timeout_with_headline_record_continues(bench):
    # the round-4 window-1 shape: per-size timeout fires AFTER the
    # headline path streamed its record -> keep the size, keep sweeping
    def child(log_n):
        if log_n == 16:
            return (_rec(16), "", None, "timeout")
        return (_rec(log_n), "", 0, None)

    sweep, fault = bench.run_sweep([16, 18, 20], child, 100)
    assert fault is None
    assert [r["log_n"] for r in sweep] == [16, 18, 20]
    assert sweep[0]["partial"] and not sweep[1].get("partial")


def test_timeout_without_record_stops(bench):
    child = lambda log_n: ("", "", None, "timeout")
    sweep, fault = bench.run_sweep([16, 18], child, 100)
    assert sweep == []
    assert fault == {"log_n": 16, "error": "timeout"}


def test_crash_keeps_salvage_and_stops(bench):
    child = lambda log_n: (_rec(log_n), "boom\ndied horribly", 1, None)
    sweep, fault = bench.run_sweep([16, 18], child, 100)
    assert fault["log_n"] == 16 and "died horribly" in fault["error"]
    assert [r.get("partial") for r in sweep] == [True]


def test_unparseable_output_stops(bench):
    child = lambda log_n: ("not json at all", "", 0, None)
    sweep, fault = bench.run_sweep([16], child, 100)
    assert sweep == []
    assert fault == {"log_n": 16, "error": "unparseable child output"}


def test_checkpoint_called_per_record(bench):
    seen = []
    child = lambda log_n: (_rec(log_n), "", 0, None)
    bench.run_sweep([16, 18], child, 100,
                    checkpoint=lambda s: seen.append(len(s)))
    assert seen == [1, 2]


def test_wanted_paths_defaults_and_validation(bench, monkeypatch):
    monkeypatch.delenv("SHEEP_BENCH_PATHS", raising=False)
    assert bench._wanted_paths() == ["hybrid", "host"]
    monkeypatch.setenv("SHEEP_BENCH_PATHS", "device")
    assert bench._wanted_paths() == ["device"]
    monkeypatch.setenv("SHEEP_BENCH_PATHS", "host")  # no headline path
    with pytest.raises(SystemExit):
        bench._wanted_paths()
    monkeypatch.setenv("SHEEP_BENCH_PATHS", "Hybrid")  # case typo
    with pytest.raises(SystemExit):
        bench._wanted_paths()


def test_last_record_picks_newest_record_line(bench):
    out = "\n".join(["garbage", _rec(16, 1.0), "noise", _rec(16, 2.0),
                     json.dumps({"no_eps": True})])
    assert bench.last_record(out)["edges_per_sec"] == 2.0
    assert bench.last_record(b"") is None
    assert bench.last_record(None) is None


def test_bench_without_tpu_fails_and_prints_no_number(tmp_path):
    """A run that finds no chip fails: under JAX_PLATFORMS=cpu the child
    refuses to measure, and the parent exits non-zero with no record."""
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu", SHEEP_BENCH_LOG_N="10",
               SHEEP_BENCH_TIMEOUT="240")
    proc = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                          capture_output=True, text=True, env=env,
                          timeout=300, cwd=str(tmp_path))
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr
