"""A configuration's graph comes from the generator its ``generator`` key
names (``generators/<name>.py``), and the harness refuses a configuration
it would not honour exactly; graph2tree spans the cell's chips."""

import json
import os

import numpy as np
import pytest

from conftest import REPO, TINY, cpu_devices, last_json, load_run, make_root

#: a family a later PR could add with new files alone: a ring over
#: ``vertices`` slots, its labels drawn from the seed
RING = '''
import numpy as np

KEYS = {"vertices"}


def edges(config, seed):
    n = config["vertices"]
    label = np.random.default_rng(seed).permutation(n).astype(np.uint32)
    v = np.arange(n)
    return label[v], label[(v + 1) % n]
'''


def _edit_json(path, **changes):
    with open(path) as f:
        data = json.load(f)
    data.update(changes)
    with open(path, "w") as f:
        json.dump(data, f)


def _tiny_config(root):
    return os.path.join(root, "benchmark", "configs", "tiny.json")


def test_every_cell_of_the_benchmark_is_honoured():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        man = json.load(f)
    run = load_run(REPO)
    for w in man["workloads"]:
        run.load_cell(REPO, w["name"])


@pytest.mark.parametrize("seed", [7, 3_000_000_019])
def test_kronecker_adapter_is_kron_edges(tiny_root, seed):
    import kron
    run = load_run(tiny_root)
    with open(_tiny_config(tiny_root)) as f:
        cfg = json.load(f)
    got = run.load_generator(cfg).edges(cfg, seed)
    a, b, c = cfg["initiator"]
    want = kron.edges(TINY["scale"], TINY["edgefactor"], a, b, c,
                      cfg["graph_seed"], seed)
    for g, w in zip(got, want):
        assert g.dtype == np.uint32
        np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("change,named", [
    ({"generator": "no-such"}, "no-such"),
    ({"generator": "../run"}, "../run"),
    ({"radius": 0.55}, "radius")])
def test_a_config_the_harness_would_not_honour_is_refused(tmp_path, capsys,
                                                          change, named):
    """Refused by name before any device work: the run exits non-zero,
    says why on stderr and prints no result."""
    root = make_root(str(tmp_path / "root"))
    _edit_json(_tiny_config(root), **change)
    run = load_run(root)
    with pytest.raises(run.RunError, match=named):
        run.load_cell(root, "tiny.build")

    def no_device(chips):
        raise AssertionError("device work before the refusal")
    rc = run.main(["--workload", "tiny.build", "--seed", "1", "--seconds",
                   "0.5"], root=root, devices_for=no_device)
    out, err = capsys.readouterr()
    assert rc != 0 and out == ""
    assert named in err


def test_a_new_family_is_new_files_alone(tmp_path, capsys):
    """A generator, a configuration naming it and entries for its cell:
    a whole run on the CPU, compared with the plain reference."""
    root = make_root(str(tmp_path / "root"))
    bench = os.path.join(root, "benchmark")
    with open(os.path.join(bench, "generators", "ring.py"), "w") as f:
        f.write(RING)
    with open(os.path.join(bench, "configs", "ring.json"), "w") as f:
        json.dump({"name": "ring", "source": "test", "generator": "ring",
                   "vertices": 3000, "reduced": []}, f)
    man_path = os.path.join(root, "BENCHMARK.json")
    with open(man_path) as f:
        man = json.load(f)
    man["configs"].append({"name": "ring", "source": "test",
                           "file": "benchmark/configs/ring.json",
                           "reduced": [], "why": "test"})
    man["workloads"].append({"name": "ring.build-p16", "config": "ring",
                             "traffic": "build-p16", "chips": 1,
                             "why": "test"})
    for m in man["end_to_end"]:
        if "g500-s21.build-p16" in m.get("workloads", []):
            m["workloads"].append("ring.build-p16")
    with open(man_path, "w") as f:
        json.dump(man, f)
    run = load_run(root)
    rc = run.main(["--workload", "ring.build-p16", "--seed",
                   "3000000019", "--seconds", "0.5"], root=root,
                  devices_for=cpu_devices)
    out, err = capsys.readouterr()
    assert rc == 0, err
    got = last_json(out)
    assert got["correct"] is True, err
    assert set(got["metrics"]) == {"tree_s", "partition_s", "setup_s"}


@pytest.mark.parametrize("chips", [1, 4])
def test_graph2tree_spans_the_cells_chips(tmp_path, capsys, monkeypatch,
                                          chips):
    root = make_root(str(tmp_path / "root"))
    man_path = os.path.join(root, "BENCHMARK.json")
    with open(man_path) as f:
        man = json.load(f)
    for w in man["workloads"]:
        if w["name"] == "tiny.build":
            w["chips"] = chips
    with open(man_path, "w") as f:
        json.dump(man, f)
    from sheep_tpu.cli import graph2tree
    seen, main = [], graph2tree.main

    def spy(argv):
        seen.append(os.environ.get("SHEEP_WORKERS"))
        return main(argv)
    monkeypatch.setattr(graph2tree, "main", spy)
    run = load_run(root)
    rc = run.main(["--workload", "tiny.build", "--seed", "11", "--seconds",
                   "0.5"], root=root, devices_for=cpu_devices)
    out, err = capsys.readouterr()
    assert rc == 0, err
    assert last_json(out)["correct"] is True, err
    assert len(seen) >= 2 and set(seen) == {str(chips)}
