"""The DIMACS10 random geometric graph (``generators/dimacs10-rgg.py``):
its edges are the definition's, every seed relabels the same graph, the
harness honours its configuration, and a small cell of it runs whole;
and the reader of the fold metric that came with it."""

import json
import os

import numpy as np
import pytest

from conftest import REPO, cpu_devices, last_json, load_run, make_root

CELL = "rgg-n22.build-p16"


def _rgg_config(root=REPO):
    with open(os.path.join(root, "benchmark", "configs", "rgg-n22.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def rgg():
    return load_run(REPO).load_generator(_rgg_config())


def _undirected(i, j):
    i, j = np.asarray(i, np.int64), np.asarray(j, np.int64)
    return np.minimum(i, j), np.maximum(i, j)


@pytest.mark.parametrize("log2_vertices", [10, 11])
def test_edges_are_the_definitions(rgg, log2_vertices):
    """Every pair closer than the radius, each once, and no other: the
    grid search against an O(n^2) pass over all pairs."""
    n = 1 << log2_vertices
    xy = rgg.points(n, 0)
    r = rgg.radius(n, 0.55)
    i, j = rgg.pairs(xy, r)
    assert not np.any(i == j)
    lo, hi = _undirected(i, j)
    key = lo * n + hi
    assert len(np.unique(key)) == len(key)
    d = xy[:, None, :] - xy[None, :, :]
    near = np.triu(d[..., 0] * d[..., 0] + d[..., 1] * d[..., 1] < r * r, 1)
    bi, bj = np.nonzero(near)
    np.testing.assert_array_equal(np.sort(key), bi * n + bj)


def test_seeds_relabel_the_same_graph(rgg):
    """Two seeds: the same edge count and degree multiset, in other
    labels and another order."""
    cfg = dict(_rgg_config(), log2_vertices=12)
    n = 1 << 12
    got = [rgg.edges(cfg, s) for s in (11, 3_000_000_019)]
    for t, h in got:
        assert t.dtype == np.uint32 and h.dtype == np.uint32
        assert t.max() < n and h.max() < n
    (t0, h0), (t1, h1) = got
    assert len(t0) == len(t1) > 0
    deg0 = np.bincount(np.concatenate([t0, h0]), minlength=n)
    deg1 = np.bincount(np.concatenate([t1, h1]), minlength=n)
    np.testing.assert_array_equal(np.sort(deg0), np.sort(deg1))
    assert not np.array_equal(t0, t1)


def test_the_cell_is_honoured_and_an_unread_key_refused(tmp_path):
    run = load_run(REPO)
    cell = run.load_cell(REPO, CELL)
    assert cell.generator.KEYS == {"log2_vertices", "radius_factor",
                                   "graph_seed"}
    assert cell.traffic["parts"] == [16]
    assert {m["name"] for m in cell.e2e} == {"tree_s", "partition_s",
                                             "setup_s"}
    root = make_root(str(tmp_path / "root"), tiny=False)
    path = os.path.join(root, "benchmark", "configs", "rgg-n22.json")
    with open(path, "w") as f:
        json.dump(dict(_rgg_config(), torus=True), f)
    run = load_run(root)
    with pytest.raises(run.RunError, match="torus"):
        run.load_cell(root, CELL)


def test_a_small_rgg_cell_runs_whole(tmp_path, capsys):
    """2^14 points through run.main on the CPU: every job equal to the
    plain reference."""
    root = make_root(str(tmp_path / "root"), tiny=False)
    path = os.path.join(root, "benchmark", "configs", "rgg-n22.json")
    with open(path, "w") as f:
        json.dump(dict(_rgg_config(), log2_vertices=14), f)
    run = load_run(root)
    rc = run.main(["--workload", CELL, "--seed", "3000000019", "--seconds",
                   "0.5"], root=root, devices_for=cpu_devices)
    out, err = capsys.readouterr()
    assert rc == 0, err
    got = last_json(out)
    assert got["correct"] is True, err
    assert all(c["value"] == 0 for c in got["checks"].values())
    assert set(got["metrics"]) == {"tree_s", "partition_s", "setup_s"}


def _span(name, attrs, dur=0.1):
    return {"k": "span", "name": name, "id": 1, "par": None, "tid": 1,
            "t": 0.0, "dur": dur, "cpu": dur, "a": attrs}


@pytest.mark.parametrize("with_folds", [True, False])
def test_the_fold_reader(with_folds):
    """Per call, the fold spans summed; silent on a program whose calls
    record no fold."""
    run = load_run(REPO)
    tree = [[_span("handoff.fold", {"window": k}, 0.25) for k in range(4)],
            [_span("handoff.fold", {"window": 0}, 0.5)]]
    obs = run.Observed(tree if with_folds else [[], []], [], None, None)
    fold = run.load_reader("handoff.fold_s")(obs)
    if with_folds:
        assert fold == pytest.approx(0.75)
    else:
        assert fold is None
