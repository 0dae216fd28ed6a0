"""Direct tests for the scripts/lib.sh helpers.

The shell orchestration layer was only ever exercised indirectly (whole
dist-partition.sh runs, tests/test_cli.py); these tests drive each helper
through a bash -c subprocess so a regression in sheep_wait_all's failure
propagation or sheep_mv_artifact's sidecar-first ordering fails HERE with
a readable assertion instead of as a flaky end-to-end hang.
"""

import os
import subprocess
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LIB = os.path.join(REPO, "scripts", "lib.sh")

pytestmark = pytest.mark.skipif(not os.path.exists(LIB),
                                reason="scripts/lib.sh not present")


def bash(snippet: str, timeout: float = 60, env_extra: dict | None = None):
    env = dict(os.environ)
    if env_extra:
        env.update(env_extra)
    return subprocess.run(
        ["bash", "-c", f"source {LIB}\n{snippet}"],
        capture_output=True, text=True, timeout=timeout, env=env)


# ---------------------------------------------------------------------------
# sheep_wait_all: a crashed worker must fail the phase
# ---------------------------------------------------------------------------


def test_wait_all_success():
    proc = bash("(exit 0) & p1=$!; (exit 0) & p2=$!\n"
                "sheep_wait_all $p1 $p2")
    assert proc.returncode == 0, proc.stderr


def test_wait_all_propagates_any_failure():
    proc = bash("(exit 0) & p1=$!; (exit 3) & p2=$!; (exit 0) & p3=$!\n"
                "sheep_wait_all $p1 $p2 $p3")
    assert proc.returncode == 1
    assert "failed" in proc.stderr


def test_wait_all_reports_every_failed_pid():
    proc = bash("(exit 1) & p1=$!; (exit 2) & p2=$!\n"
                "sheep_wait_all $p1 $p2")
    assert proc.returncode == 1
    assert proc.stderr.count("failed") == 2


# ---------------------------------------------------------------------------
# sheep_mv_artifact: artifact + sidecar travel together, sidecar first
# ---------------------------------------------------------------------------


def test_mv_artifact_moves_sidecar_too(tmp_path):
    src, dst = tmp_path / "a.tre", tmp_path / "b.tre"
    src.write_bytes(b"tree")
    (tmp_path / "a.tre.sum").write_text("sheep-sum 1\n")
    proc = bash(f"sheep_mv_artifact {src} {dst}")
    assert proc.returncode == 0, proc.stderr
    assert dst.read_bytes() == b"tree"
    assert (tmp_path / "b.tre.sum").exists()
    assert not src.exists() and not (tmp_path / "a.tre.sum").exists()


def test_mv_artifact_without_sidecar(tmp_path):
    src, dst = tmp_path / "a.seq", tmp_path / "b.seq"
    src.write_bytes(b"seq")
    proc = bash(f"sheep_mv_artifact {src} {dst}")
    assert proc.returncode == 0, proc.stderr
    assert dst.read_bytes() == b"seq"
    assert not (tmp_path / "b.seq.sum").exists()


def test_mv_artifact_sidecar_lands_before_artifact(tmp_path):
    # The ordering IS the contract (a polling consumer that sees the
    # artifact must see its checksum): with the artifact itself missing,
    # a failing sheep_mv_artifact must already have moved the sidecar —
    # artifact-first ordering would fail before touching it.
    (tmp_path / "a.tre.sum").write_text("sheep-sum 1\n")
    proc = bash(f"sheep_mv_artifact {tmp_path}/a.tre {tmp_path}/b.tre")
    assert proc.returncode != 0  # the artifact mv failed...
    assert (tmp_path / "b.tre.sum").exists()  # ...after the sidecar moved


# ---------------------------------------------------------------------------
# sheep_wait_for: blocks until the artifact appears
# ---------------------------------------------------------------------------


def test_wait_for_appearing_file(tmp_path):
    target = tmp_path / "late.tre"
    proc = bash(
        f"( sleep 0.3; touch {target} ) &\n"
        f"sheep_wait_for {target} {tmp_path}\n"
        f"[ -f {target} ]",
        env_extra={"USE_INOTIFY": "1"})  # the sleep-poll path
    assert proc.returncode == 0, proc.stderr


# ---------------------------------------------------------------------------
# sheep_heartbeat_start/stop: the liveness beat (supervisor contract)
# ---------------------------------------------------------------------------


def test_heartbeat_beats_and_stops(tmp_path):
    hb = tmp_path / "w.hb"
    proc = bash(
        f"sheep_heartbeat_start {hb}\n"
        f"sleep 0.3\n"
        f"[ -f {hb} ] || exit 9\n"
        f"sheep_heartbeat_stop\n"
        f"stat -c %Y.%N {hb} > {tmp_path}/t1 2>/dev/null || "
        f"stat -c %Y {hb} > {tmp_path}/t1\n"
        f"sleep 0.4\n"
        f"stat -c %Y.%N {hb} > {tmp_path}/t2 2>/dev/null || "
        f"stat -c %Y {hb} > {tmp_path}/t2\n",
        env_extra={"SHEEP_HEARTBEAT_S": "0.1"})
    assert proc.returncode == 0, proc.stderr
    # after stop, the mtime must not advance
    assert (tmp_path / "t1").read_text() == (tmp_path / "t2").read_text()


def test_heartbeat_mtime_advances_while_alive(tmp_path):
    hb = tmp_path / "w.hb"
    proc = bash(
        f"sheep_heartbeat_start {hb}\n"
        f"sleep 0.15; m1=$(stat -c %y {hb})\n"
        f"sleep 0.3; m2=$(stat -c %y {hb})\n"
        f"sheep_heartbeat_stop\n"
        f'[ "$m1" != "$m2" ]',
        env_extra={"SHEEP_HEARTBEAT_S": "0.1"})
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_heartbeat_start_without_path_is_noop():
    proc = bash('sheep_heartbeat_start ""\nsheep_heartbeat_stop')
    assert proc.returncode == 0, proc.stderr


def test_heartbeat_loop_dies_with_shell(tmp_path):
    # kill -9 the owning shell; the beat loop must self-terminate (kill -0
    # check) instead of beating on a dead worker's behalf forever.  No
    # pipe capture here: the orphaned loop inherits them and a capturing
    # run() would block on EOF while the dead shell is still a zombie.
    hb = tmp_path / "w.hb"
    script = (f"source {LIB}\n"
              f"sheep_heartbeat_start {hb}\n"
              f"sleep 0.15\n"
              f"kill -9 $$\n")
    proc = subprocess.run(["bash", "-c", script], timeout=60,
                          stdout=subprocess.DEVNULL,
                          stderr=subprocess.DEVNULL,
                          env=dict(os.environ, SHEEP_HEARTBEAT_S="0.1"))
    assert proc.returncode != 0  # SIGKILLed
    time.sleep(0.3)  # give a hypothetical orphan time to notice / beat
    try:
        m1 = os.path.getmtime(hb)
    except OSError:
        return  # never beat at all: also silent, also fine
    time.sleep(0.4)
    assert os.path.getmtime(hb) == m1, \
        "orphaned heartbeat loop kept beating after its worker died"


# ---------------------------------------------------------------------------
# sheep_mesh_graph2tree: one process per chip
# ---------------------------------------------------------------------------


def test_mesh_multiprocess_refused_off_cpu(tmp_path):
    """SHEEP_PROCS > 1 starts its processes on this host; off the cpu
    emulation each would claim every local chip, so the launcher refuses
    with a message and starts nothing."""
    marker = tmp_path / "started"
    stub = tmp_path / "graph2tree"
    stub.write_text(f"#!/bin/sh\ntouch {marker}\n")
    stub.chmod(0o755)
    proc = bash(f'SHEEP_BIN={tmp_path} sheep_mesh_graph2tree x',
                env_extra={"SHEEP_PROCS": "2", "JAX_PLATFORMS": "tpu"})
    assert proc.returncode == 2
    assert "claim every local chip" in proc.stderr
    assert not marker.exists()
