"""chip_smoke.py's phases on the host backend at a small size.

The smoke's phases are plain functions, so the CPU suite runs them at
n = 2^12 against the native oracle (one device: the hybrid build; four
virtual devices: the chunked mesh build).  The script itself must refuse
to run without a TPU, and outside a checkout of this repo.
"""

import importlib.util
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("workers", [1, 4])
def test_rmat_phase_matches_native(smoke, tmp_path, workers):
    got = smoke.phase_rmat(str(tmp_path), 12, 8 << 12, seed=3,
                           workers=workers)
    if workers == 1:
        hand = got["events"]["hybrid.handoff"][-1]
        assert "fallback" not in str(hand.get("stream_mode", ""))
    else:
        assert got["events"]["mesh.stage"][-1]["devices"] == 4
    assert len(got["seq"]) == len(got["parent"]) == len(got["pst"])


def test_hep_th_phase_prints_goldens(smoke, tmp_path):
    got = smoke.phase_hep_th(REPO, str(tmp_path))
    assert got["faqs"] == smoke.HEP_TREEFAQS
    assert got["ecv_down"] == 521 and got["sizes"] == (3409, 4201)


def test_compare_names_every_mismatch(smoke):
    a = {"seq": np.arange(4), "parent": np.zeros(4), "pst": np.ones(4),
         "sizes": (2, 2), "ecv_down": 5}
    b = dict(a, parent=np.ones(4), ecv_down=6)
    assert smoke.compare(a, dict(a)) == []
    bad = smoke.compare(a, b)
    assert "parent" in bad and any(s.startswith("ecv_down") for s in bad)


@pytest.mark.parametrize("workers, events, match", [
    (4, {"mesh.stage": [{"devices": 1, "workers": 4}],
         "mesh.build": [{"reduce_rounds": 3, "prep_s": 0.0,
                         "reduce_s": 0.0}]}, "1 devices"),
    (1, {"hybrid.handoff": [{"kind": "forest", "rounds": 2, "live": 9}]},
     "not the windowed stream"),
    (1, {}, "device path did not run"),
])
def test_report_fails_on_a_path_the_chip_must_not_take(smoke, workers,
                                                       events, match):
    with pytest.raises(smoke.SmokeFailure, match=match):
        smoke._report_build({"events": events}, workers)


def _run(argv, cwd, env):
    return subprocess.run([sys.executable] + argv, capture_output=True,
                          text=True, timeout=300, cwd=cwd, env=env)


def test_smoke_exits_nonzero_without_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = _run([SMOKE, "--out", str(tmp_path)], str(tmp_path), env)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_smoke_alone_outside_checkout_fails(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = _run([str(tmp_path / "chip_smoke.py")], str(tmp_path), env)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
