"""The program's own spans: one root per command, the layers as its
direct children, CPU time on every span, the prep span ended when its
device output is ready, threads parented to the span that started them,
compile events, and the spans on the profiler's clock.  Off, the
recorder is the shared no-op and imports nothing.

graph2tree runs ``-i -r`` on the path the chip takes as far as the CPU
backend can be made to take it: the device prep (``prepare_links``, not
the host sequence) and the device window stream of the handoff.
"""

import contextlib
import io
import json
import os
import subprocess
import sys
import threading
import time

import pytest

from sheep_tpu.obs import trace as obs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: the chip's path on the CPU backend
CHIP_PATH = {"SHEEP_WORKERS": "1", "SHEEP_STREAM_HOST_SEQ": "0",
             "SHEEP_STREAM_DEVICE_WINDOWS": "1", "SHEEP_HANDOFF_WINDOWS": "2"}
#: the graph2tree root's direct children on that path
TREE_LAYERS = {"io.load", "prep", "reduce.loop", "handoff.fetch",
               "handoff.fold", "build.selfcheck", "io.write"}
#: partition.evaluate's children: the bitmap paths (in memory and streamed)
#: and the sort body that runs without the native runtime
EVAL_STEPS = {"bitmap": {"evaluate.scan", "evaluate.finish"},
              "sort": {"evaluate.gather", "evaluate.vcom", "evaluate.ecv_hash",
                       "evaluate.ecv_seq"}}


@pytest.fixture(autouse=True)
def _no_trace():
    prev = os.environ.pop(obs.ENV, None)
    obs.close_recorder()
    yield
    obs.close_recorder()
    if prev is not None:
        os.environ[obs.ENV] = prev


@pytest.fixture(scope="module")
def graph(tmp_path_factory):
    from sheep_tpu.io.edges import write_dat
    from sheep_tpu.utils.synth import rmat_edges
    d = tmp_path_factory.mktemp("spans")
    tail, head = rmat_edges(15, 16 << 15, seed=7)
    dat = str(d / "g.dat")
    write_dat(dat, tail, head)
    return str(d), dat


def _call(main, argv, trace=None):
    """Run a command in-process; with ``trace``, under the recorder, and
    return its records."""
    if trace:
        os.environ[obs.ENV] = trace
    try:
        with contextlib.redirect_stdout(io.StringIO()):
            assert main(argv) == 0
    finally:
        if trace:
            obs.close_recorder()
            os.environ.pop(obs.ENV, None)
    if trace:
        records, _, torn = obs.read_trace(trace, "strict")
        assert not torn
        return [r for r in records if r["k"] != "meta"]
    return None


def _spans(records):
    return [r for r in records if r["k"] == "span"]


def _root(records, name):
    (root,) = [r for r in _spans(records) if r["name"] == name]
    kids = [r for r in _spans(records) if r["par"] == root["id"]]
    return root, kids


def _unexplained(root, kids):
    """The root's time that its children on its own thread leave bare."""
    lo, hi = root["t"], root["t"] + root["dur"]
    covered, end = 0.0, lo
    for a, b in sorted((k["t"], k["t"] + k["dur"]) for k in kids
                       if k["tid"] == root["tid"]):
        a, b = max(a, end), min(b, hi)
        if b > a:
            covered += b - a
            end = b
    return root["dur"] - covered


def _graph2tree(monkeypatch, graph, trace=None):
    from sheep_tpu.cli import graph2tree
    d, dat = graph
    for k, v in CHIP_PATH.items():
        monkeypatch.setenv(k, v)
    return _call(graph2tree.main, [dat, "-i", "-r", "-s", f"{d}/g.seq",
                                   "-o", f"{d}/g.tre"], trace)


# -- off means off -----------------------------------------------------------


def test_tracing_off_is_the_noop_and_starts_nothing(monkeypatch):
    assert obs.span("cli.graph2tree") is obs.NOOP_SPAN

    def no_clock():
        raise AssertionError("thread_time called with tracing off")
    monkeypatch.setattr(obs.time, "thread_time", no_clock)

    class Array:
        def block_until_ready(self):
            raise AssertionError("waited with tracing off")

    def work():
        pass
    threads = threading.active_count()
    with obs.span("prep") as sp:
        sp.end_when_ready(Array())
        assert obs.bind(work) is work
    assert threading.active_count() == threads
    assert obs.trace_summary() is None


def test_obs_never_imports_jax():
    """Neither importing the recorder nor recording spans, events and
    bound threads brings jax in: the serve tier runs without it."""
    prog = (
        "import os, sys, tempfile, threading\n"
        "from sheep_tpu.obs import trace as obs\n"
        "os.environ[obs.ENV] = os.path.join(tempfile.mkdtemp(), 'x.trace')\n"
        "with obs.span('a'):\n"
        "    obs.event('e')\n"
        "    t = threading.Thread(target=obs.bind(lambda: None))\n"
        "    t.start(); t.join()\n"
        "obs.close_recorder()\n"
        "assert 'jax' not in sys.modules, 'obs imported jax'\n")
    env = dict(os.environ, PYTHONPATH=REPO)
    env.pop(obs.ENV, None)
    p = subprocess.run([sys.executable, "-c", prog], env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr


# -- the recorder's new fields ----------------------------------------------


def test_cpu_time_separates_work_from_waiting(tmp_path):
    os.environ[obs.ENV] = str(tmp_path / "cpu.trace")
    with obs.span("sleep"):
        time.sleep(0.1)
    with obs.span("spin"):
        t = time.perf_counter()
        while time.perf_counter() - t < 0.1:
            pass
    obs.close_recorder()
    recs, _, _ = obs.read_trace(str(tmp_path / "cpu.trace"))
    by = {r["name"]: r for r in recs if r["k"] == "span"}
    assert by["sleep"]["dur"] >= 0.1 and by["sleep"]["cpu"] < 0.05
    assert by["spin"]["cpu"] >= 0.05
    assert by["spin"]["cpu"] <= by["spin"]["dur"] + 1e-3


def test_a_bound_thread_names_the_span_that_started_it(tmp_path):
    os.environ[obs.ENV] = str(tmp_path / "bind.trace")

    def child():
        with obs.span("on.thread"):
            obs.event("thread.event")
    with obs.span("starter"):
        t = threading.Thread(target=obs.bind(child))
        t.start()
        t.join(30)
        assert not t.is_alive()
    unbound = threading.Thread(target=child)
    unbound.start()
    unbound.join(30)
    obs.close_recorder()
    recs, _, _ = obs.read_trace(str(tmp_path / "bind.trace"))
    starter = [r for r in recs if r.get("name") == "starter"][0]
    bound, loose = [r for r in recs if r.get("name") == "on.thread"]
    assert bound["par"] == starter["id"] and bound["tid"] != starter["tid"]
    assert loose["par"] is None
    ev = [r for r in recs if r.get("name") == "thread.event"]
    assert ev[0]["par"] == bound["id"]


def test_end_when_ready_ends_at_readiness_off_the_caller(tmp_path):
    os.environ[obs.ENV] = str(tmp_path / "ready.trace")

    class Array:
        def __init__(self):
            self.waited_on = None

        def block_until_ready(self):
            self.waited_on = threading.current_thread()
            time.sleep(0.2)
            return self

    a = Array()
    with obs.span("outer"):
        t = time.perf_counter()
        with obs.span("prep") as sp:
            sp.end_when_ready(a)
        left = time.perf_counter() - t
    obs.close_recorder()  # waits for the waiter
    assert left < 0.15  # the caller did not wait
    assert a.waited_on is not threading.main_thread()
    recs, _, _ = obs.read_trace(str(tmp_path / "ready.trace"))
    by = {r["name"]: r for r in recs if r["k"] == "span"}
    assert by["prep"]["dur"] >= 0.2 and by["prep"]["cpu"] < 0.1
    assert by["prep"]["par"] == by["outer"]["id"]
    # the record names the thread that opened the span, not the waiter
    assert by["prep"]["tid"] == by["outer"]["tid"]


def test_each_compile_is_one_event(tmp_path):
    import jax
    import jax.numpy as jnp
    os.environ[obs.ENV] = str(tmp_path / "compile.trace")

    def fresh_program_for_the_compile_event(x):
        return jnp.cumsum(x * 3 + 1)
    with obs.span("outer"):
        jax.jit(fresh_program_for_the_compile_event)(
            jnp.arange(37)).block_until_ready()
    obs.close_recorder()
    recs, _, _ = obs.read_trace(str(tmp_path / "compile.trace"))
    outer = [r for r in recs if r.get("name") == "outer"][0]
    got = [r for r in recs if r.get("name") == "jax.compile"
           and "fresh_program" in r["a"]["fun_name"]]
    assert len(got) == 1
    assert got[0]["par"] == outer["id"]
    assert got[0]["a"]["s"] > 0 and isinstance(got[0]["a"]["cached"], bool)
    # no recorder open: the listener emits nothing and raises nothing
    jax.jit(lambda x: x - 5)(jnp.arange(3)).block_until_ready()


# -- the commands ----------------------------------------------------------


def test_graph2tree_root_and_its_layers(monkeypatch, graph, tmp_path):
    _graph2tree(monkeypatch, graph)  # compile outside the traced call
    recs = _graph2tree(monkeypatch, graph, str(tmp_path / "g.trace"))
    root, kids = _root(recs, "cli.graph2tree")
    assert root["par"] is None
    names = {k["name"] for k in kids}
    assert TREE_LAYERS <= names, names
    # threads the build starts name the span that started them
    assert {"prep.host", "fetch.slice"} <= names
    assert _unexplained(root, kids) <= 0.10 * root["dur"]
    for s in _spans(recs):
        assert 0 <= s["cpu"] <= s["dur"] + 1e-3, s
    load = [k for k in kids if k["name"] == "io.load"][0]
    assert load["a"]["records"] == 16 << 15 and load["a"]["bytes"] > 0
    writes = {k["a"]["kind"]: k["a"]["bytes"] for k in kids
              if k["name"] == "io.write"}
    assert set(writes) == {"seq", "tre"} and min(writes.values()) > 0
    # the replaced span and event fields are gone
    assert "prep.device" not in {s["name"] for s in _spans(recs)}
    (hand,) = [r for r in recs if r.get("name") == "hybrid.handoff"]
    assert {"rounds", "live", "handoff_links"} <= set(hand["a"])
    assert not {"loop_s", "fetch_tail_s"} & set(hand["a"])


@pytest.mark.parametrize("streamed,kernel", [(False, "bitmap"),
                                             (True, "bitmap"),
                                             (False, "sort")],
                         ids=["False", "True", "sort"])
def test_partition_tree_evaluate_and_its_steps(monkeypatch, graph, tmp_path,
                                               streamed, kernel):
    from sheep_tpu import native
    from sheep_tpu.cli import partition_tree
    _graph2tree(monkeypatch, graph)
    d, dat = graph
    monkeypatch.setenv("SHEEP_EVAL_STREAM", "1" if streamed else "0")
    if kernel == "sort":
        monkeypatch.setattr(native, "available", lambda: False)
    recs = _call(partition_tree.main, ["-g", dat, f"{d}/g.seq",
                                       f"{d}/g.tre", "16"],
                 str(tmp_path / "p.trace"))
    root, kids = _root(recs, "cli.partition_tree")
    assert {"io.read", "io.load", "partition.tree",
            "partition.evaluate"} <= {k["name"] for k in kids}
    assert {k["a"]["kind"] for k in kids if k["name"] == "io.read"} == \
        {"seq", "tre"}
    (ev,) = [k for k in kids if k["name"] == "partition.evaluate"]
    want = {"records": 16 << 15, "keys": 32 << 15, "parts": 16,
            "streamed": streamed}
    if not streamed:
        want["kernel"] = kernel
    assert ev["a"] == want
    steps = [s for s in _spans(recs) if s["par"] == ev["id"]]
    assert {s["name"] for s in steps} == EVAL_STEPS[kernel]
    if kernel == "bitmap":
        (scan,) = [s for s in steps if s["name"] == "evaluate.scan"]
        attrs = dict(scan["a"])
        threads = attrs.pop("threads")
        assert len(attrs.pop("thread_busy_s", [0])) == threads >= 1
        assert attrs == ({"windows": 1} if streamed else
                         {"windows": 1, "passes": 1, "source": "memory"})
    assert sum(s["dur"] for s in steps) <= ev["dur"]
    for s in steps + [ev]:
        assert 0 <= s["cpu"] <= s["dur"] + 1e-3, s
    (tree,) = [k for k in kids if k["name"] == "partition.tree"]
    ffd = [s for s in _spans(recs) if s["name"] == "native.forward_partition"]
    # without the runtime the FFD runs in numpy, under no native span
    assert bool(ffd) == (kernel == "bitmap")
    assert all(s["par"] == tree["id"] for s in ffd)
    assert _unexplained(root, kids) <= 0.10 * root["dur"]


def test_spans_on_the_profilers_clock(monkeypatch, graph, tmp_path):
    """Under a profiler capture, each program span sits on the host plane
    inside the outer annotation, as long as its flight record says."""
    import jax
    sys.path.insert(0, os.path.join(REPO, "benchmark"))
    import tracereduce
    _graph2tree(monkeypatch, graph)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    prof = str(tmp_path / "profile")
    jax.profiler.start_trace(prof, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("graph2tree"):
            recs = _graph2tree(monkeypatch, graph, str(tmp_path / "g.trace"))
    finally:
        jax.profiler.stop_trace()
    spans = _spans(recs)
    names = {s["name"] for s in spans}
    assert not names & {"graph2tree", "partition_tree"}
    flat = tracereduce.flatten(prof, ("graph2tree",) + tuple(sorted(names)))
    host = [e for p in flat["planes"] for line in p["lines"]
            for e in line["events"]]
    (outer,) = [e for e in host if e[0] == "graph2tree"]
    lo, hi = outer[1], outer[1] + outer[2]
    for name in names:
        got = sorted(e[2] / 1e9 for e in host if e[0] == name)
        want = sorted(s["dur"] for s in spans if s["name"] == name)
        assert len(got) == len(want), name
        assert all(abs(g - w) <= 2e-3 for g, w in zip(got, want)), name
        assert all(lo <= e[1] and e[1] + e[2] <= hi
                   for e in host if e[0] == name), name


def test_trace_records_are_plain_json(tmp_path):
    os.environ[obs.ENV] = str(tmp_path / "j.trace")
    with obs.span("a", n=3):
        pass
    obs.close_recorder()
    with open(tmp_path / "j.trace") as f:
        lines = [json.loads(line) for line in f]
    span = [r for r in lines if r["k"] == "span"][0]
    assert set(span) == {"k", "name", "id", "par", "tid", "t", "dur", "cpu",
                         "a"}
