"""A near-planar graph through the accelerator's build policy: the
hybrid build of a random geometric graph, device rounds to the 3n
handoff and then the windowed stream into the resumable native fold,
equals the native host build."""

import os

import numpy as np
import pytest

from sheep_tpu import native
from sheep_tpu.obs import trace as obs

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="needs the native runtime")

#: the accelerator's handoff policy, forced on the CPU backend: device
#: prep and reduce rounds down to 3n, then the 6-byte packed, four-window
#: device stream into the resumable fold
CHIP_POLICY = {"SHEEP_HANDOFF_FACTOR": "3", "SHEEP_HANDOFF_WINDOWS": "4",
               "SHEEP_STREAM_DEVICE_WINDOWS": "1",
               "SHEEP_STREAM_HOST_SEQ": "0", "SHEEP_PACK_HANDOFF": "1",
               "SHEEP_PIPELINE_CHUNKS": "1", "SHEEP_OVERLAP_SLICE": "1024"}


def _records(path):
    obs.close_recorder()
    os.environ.pop(obs.ENV, None)
    records, _, torn = obs.read_trace(path, "strict")
    assert not torn
    return records


@pytest.fixture
def recorder(monkeypatch, tmp_path):
    """A flight-recorder file for the test; its records once closed."""
    obs.close_recorder()
    path = str(tmp_path / "t.trace")
    monkeypatch.setenv(obs.ENV, path)
    yield lambda: _records(path)
    obs.close_recorder()


def rgg_edges(n, seed, radius_factor=0.55):
    """Every pair of ``n`` seeded uniform points in the unit square closer
    than factor * sqrt(ln n / n), once each, in a seeded order."""
    rng = np.random.default_rng(seed)
    xy = rng.random((n, 2))
    r2 = radius_factor ** 2 * np.log(n) / n
    ti, hi = [], []
    for a in range(0, n, 512):
        d = xy[a:a + 512, None, :] - xy[None, :, :]
        near = d[..., 0] ** 2 + d[..., 1] ** 2 < r2
        i, j = np.nonzero(near)
        i += a
        keep = i < j
        ti.append(i[keep])
        hi.append(j[keep])
    tail = np.concatenate(ti).astype(np.uint32)
    head = np.concatenate(hi).astype(np.uint32)
    order = rng.permutation(len(tail))
    return tail[order], head[order]


@pytest.mark.parametrize("seed,radius_factor", [
    (3, 0.55), (2_000_000_011, 0.55), (5, 0.3)])
def test_hybrid_build_of_an_rgg_under_the_chip_policy_is_the_native_build(
        monkeypatch, recorder, seed, radius_factor):
    """At the DIMACS10 radius, and at a sparser one that leaves isolated
    vertices and many roots."""
    import sheep_tpu.ops.build as B
    from sheep_tpu.core import build_forest, degree_sequence
    from sheep_tpu.ops import build_graph_hybrid
    for k, v in CHIP_POLICY.items():
        monkeypatch.setenv(k, v)
    # the accelerator's gate: no immediate handoff, the device reduces
    monkeypatch.setattr(B, "handoff_input_ok", lambda: False)
    n = 4096
    tail, head = rgg_edges(n, seed, radius_factor)
    perf = {}
    seq, f = build_graph_hybrid(tail, head, n, perf=perf)
    records = recorder()
    seq0 = degree_sequence(tail, head, n)
    f0 = build_forest(tail, head, seq0, impl="native")
    np.testing.assert_array_equal(seq, seq0)
    np.testing.assert_array_equal(f.parent, f0.parent)
    np.testing.assert_array_equal(f.pst_weight, f0.pst_weight)
    (handoff,) = [r["a"] for r in records if r.get("name") == "hybrid.handoff"]
    assert handoff["kind"] == "forest" and handoff["rounds"] >= 1
    assert handoff["packed_handoff"] is True
    assert perf["stream_mode"] == "windowed" and perf["fetch_windows"] == 4
    folds = [r for r in records if r.get("name") == "handoff.fold"]
    assert len(folds) == 4
    assert sum(r["a"]["links"] for r in folds) == handoff["handoff_links"]

