"""Run one benchmark cell once: ``run.py --workload W --seed S --seconds T
--trace 0|1``.

A cell is a configuration (``configs/<config>.json``: the graph) under a
traffic mix (``traffic/<mix>.json``: the job's part counts), both named by
the cell's entry in ``BENCHMARK.json``.  A job is the user's two
commands, run in this process as the user's shell would run them:
``graph2tree <dat> -i -r -s <seq> -o <tre>``, then, where the mix names
part counts, ``partition_tree -g <dat> <seq> <tre> <parts...>``.

The configuration's ``generator`` names ``generators/<generator>.py``,
whose ``edges(config, seed)`` makes the graph and whose ``KEYS`` lists
the configuration keys it reads.  A cell whose generator does not exist,
or whose configuration sets a key that neither the harness nor its
generator reads, is refused before any device work.

Set-up (``setup_s``, from process start to the first timed job): the
device check, the graph made from ``--seed`` by the configuration's
generator, its ``.dat`` written, and one warm-up ``graph2tree`` call
that compiles or loads every program the window runs.
``partition_tree`` runs no device program, so it is not warmed up: its
first call is as any other.  The window is a closed loop with one
client: jobs back to back while the elapsed time is under ``--seconds``;
every job started completes and counts.  Then the plain reference
(``plainref``) answers the same job, and every timed job's sequence,
tree, part sizes and ECV(down) must equal it.

``--trace 0`` reports the cell's end-to-end metrics.  ``--trace 1`` runs
the window under the JAX profiler and the program's flight recorder
(``SHEEP_TRACE``, one file per call) and reports the per-layer metrics,
each read by ``metrics/<name>.py``.

The last stdout line is one JSON object; the numbers compared, with their
limits, are the last lines of stderr and the last key of that object.
Without a TPU, or with fewer chips than the cell asks for, the run exits
non-zero and prints no result.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import io  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import kron  # noqa: E402
import plainref  # noqa: E402
import tracereduce  # noqa: E402

ANNOTATIONS = ("graph2tree", "partition_tree")
#: every compared number is an exact count of differences
LIMITS = {"seq": 0, "parent": 0, "pst": 0, "sizes": 0, "ecv_down": 0}
#: what a traffic mix may set: the part counts of partition_tree and its
#: balance factor (one client, SHEEP_WORKERS = the cell's chips, jobs back
#: to back).  With no part counts a job is graph2tree alone, and has no
#: balance factor.
TRAFFIC_KEYS = {"name", "parts", "balance_factor"}
#: what the harness reads of a configuration itself; every other key it
#: sets is one that its generator's ``KEYS`` lists
CONFIG_KEYS = {"name", "source", "generator", "guarantees", "reduced",
               "assumed"}


class RunError(Exception):
    """The run cannot produce a result (no chip, no program, bad cell)."""


def _process_age() -> float:
    """Seconds since this process started, from /proc."""
    with open("/proc/self/stat") as f:
        fields = f.read().rsplit(")", 1)[1].split()
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return max(0.0, uptime - int(fields[19]) / os.sysconf("SC_CLK_TCK"))


AGE0 = _process_age()


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _load_module(kind: str, name: str):
    """``<kind>/<name>.py`` of the benchmark, imported by path."""
    path = os.path.join(HERE, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"{kind}_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_generator(config: dict):
    """The module of the generator the configuration names, or RunError
    where there is none or the configuration sets a key that it and the
    harness leave unread."""
    name = config.get("generator")
    have = {f[:-3] for f in os.listdir(os.path.join(HERE, "generators"))
            if f.endswith(".py")}
    if not isinstance(name, str) or name not in have:
        raise RunError(f"configuration {config.get('name')!r} names the "
                       f"generator {name!r}, which the harness does not "
                       f"have")
    gen = _load_module("generators", name)
    unread = set(config) - CONFIG_KEYS - set(gen.KEYS)
    if unread:  # a setting the harness would silently not honour
        raise RunError(f"configuration {config.get('name')!r} sets "
                       f"{sorted(unread)}, which neither the harness nor "
                       f"the generator {name!r} reads")
    return gen


@dataclass
class Cell:
    workload: dict
    config: dict
    traffic: dict
    e2e: list[dict]
    per_layer: list[dict]
    generator: object  # the module of generators/<generator>.py


def load_cell(root: str, name: str) -> Cell:
    """The cell's entries and files, found by the names BENCHMARK.json
    gives them."""
    bench = os.path.join(root, "BENCHMARK.json")
    if not os.path.exists(bench):
        raise RunError(f"no BENCHMARK.json at {root}")
    man = _json(bench)
    cells = {w["name"]: w for w in man["workloads"]}
    if name not in cells:
        raise RunError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    cfg = next(c for c in man["configs"] if c["name"] == w["config"])
    config = _json(os.path.join(root, cfg["file"]))
    generator = load_generator(config)
    traffic = _json(os.path.join(HERE, "traffic", f"{w['traffic']}.json"))
    unread = set(traffic) - TRAFFIC_KEYS
    if not traffic.get("parts"):
        unread.add("balance_factor")
    unread &= set(traffic)
    if unread:  # a setting the harness would silently not honour
        raise RunError(f"traffic {w['traffic']!r} sets {sorted(unread)}, "
                       f"which the harness does not read")

    def mine(m):
        return name in m.get("workloads", [name])
    return Cell(w, config, traffic,
                [m for m in man["end_to_end"] if mine(m)],
                [m for m in man["per_layer"] if mine(m)], generator)


def load_reader(metric: str):
    """``metrics/<metric>.py``'s ``read(obs) -> float | None``."""
    return _load_module("metrics", metric).read


def peaks(kind: str) -> dict:
    """The chip's published peaks; a kind not in the table is an error."""
    table = _json(os.path.join(HERE, "peaks.json"))["devices"]
    if kind not in table:
        raise RunError(f"device kind {kind!r} is not in peaks.json")
    return table[kind]


def require_tpu(chips: int):
    """The chips this cell asks for, or RunError."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise RunError(f"no TPU: JAX found {devices[0].platform!r}")
    if len(devices) < chips:
        raise RunError(f"the cell needs {chips} chips, JAX found "
                       f"{len(devices)}")
    peaks(devices[0].device_kind)
    return devices


@dataclass
class Job:
    """One job's timings and what it produced."""
    started: float = 0.0  # seconds from the window's start
    tree_s: float = 0.0
    partition_s: float = 0.0
    seq_path: str = ""
    tre_path: str = ""
    sizes: dict = field(default_factory=dict)
    ecv_down: dict = field(default_factory=dict)
    error: str | None = None
    tree_records: list = field(default_factory=list)
    partition_records: list = field(default_factory=list)


@contextlib.contextmanager
def _env(**values: str | None):
    prior = {k: os.environ.get(k) for k in values}
    for k, v in values.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v
    try:
        yield
    finally:
        for k, v in prior.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _read_records(path: str) -> list[dict]:
    if not os.path.exists(path):
        return []
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


class Runner:
    """Runs jobs in this process against one graph."""

    def __init__(self, dat: str, work: str, traffic: dict, chips: int):
        from sheep_tpu.cli import graph2tree, partition_tree
        from sheep_tpu.obs import trace as flight
        from sheep_tpu.partition.partition import Partition
        self.g2t, self.pt, self.flight = graph2tree, partition_tree, flight
        self.dat, self.work = dat, work
        # graph2tree -i -r spans as many devices as SHEEP_WORKERS says
        self.workers = str(chips)
        self.parts = [int(p) for p in traffic["parts"]]
        self.balance = str(traffic.get("balance_factor"))
        # the partitions partition_tree produces, in the order it prints
        # them: every part's size is read from these
        self.made: list = []
        made, shown = self.made, Partition.print

        def _print(part):
            made.append(part.parts)
            return shown(part)
        Partition.print = _print
        self._restore = lambda: setattr(Partition, "print", shown)

    def close(self) -> None:
        self._restore()

    def _call(self, main, argv: list[str], trace: str | None,
              annotation: str, profile: bool):
        out = io.StringIO()
        ann = contextlib.nullcontext()
        if profile:
            import jax
            ann = jax.profiler.TraceAnnotation(annotation)
        with _env(SHEEP_TRACE=trace, SHEEP_WORKERS=self.workers), \
                contextlib.redirect_stdout(out), ann:
            t = time.perf_counter()
            rc = main(argv)
            dt = time.perf_counter() - t
        if trace:
            self.flight.close_recorder()
        if rc:
            raise RuntimeError(f"{annotation} {' '.join(argv)} returned "
                               f"{rc}: {out.getvalue()[-2000:]}")
        return dt, out.getvalue()

    def _partition(self, j: Job, trace: str | None, traced: bool) -> None:
        import numpy as np
        j.partition_s, out = self._call(
            self.pt.main, ["-b", self.balance, "-g", self.dat, j.seq_path,
                           j.tre_path, *map(str, self.parts)],
            trace, "partition_tree", traced)
        ecv = [int(x) for x in re.findall(r"ECV\(down\): (\d+)", out)]
        if len(ecv) != len(self.parts) or len(self.made) != len(self.parts):
            raise RuntimeError(f"partition_tree printed {len(ecv)} "
                               f"ECV(down) lines for {self.parts}")
        for p, e, part in zip(self.parts, ecv, self.made):
            j.ecv_down[p] = e
            j.sizes[p] = np.bincount(part[part >= 0])

    def job(self, tag: str, traced: bool = False,
            partition: bool = True) -> Job:
        j = Job(seq_path=os.path.join(self.work, f"{tag}.seq"),
                tre_path=os.path.join(self.work, f"{tag}.tre"))
        tt = os.path.join(self.work, f"{tag}.g.trace") if traced else None
        pt = os.path.join(self.work, f"{tag}.p.trace") if traced else None
        self.made.clear()
        try:
            j.tree_s, _ = self._call(
                self.g2t.main, [self.dat, "-i", "-r", "-s", j.seq_path,
                                "-o", j.tre_path], tt, "graph2tree", traced)
            if partition and self.parts:
                self._partition(j, pt, traced)
        except Exception:  # a failed job is counted, not fatal
            j.error = traceback.format_exc()
        self.made.clear()
        if traced:
            j.tree_records = _read_records(tt)
            j.partition_records = _read_records(pt)
        return j


def answer(job: Job) -> plainref.Answer:
    """What a job produced: its .seq (text, a vid per line) and .tre
    (uint32 count, then uint32 parent and pst per node) as written, and
    the partitions it made."""
    import numpy as np
    with open(job.seq_path, "rb") as f:
        seq = np.array(f.read().split(), dtype=np.int64)
    raw = np.fromfile(job.tre_path, dtype="<u4")
    body = raw[1:1 + 2 * int(raw[0])]
    return plainref.Answer(seq, body[0::2], body[1::2], job.sizes,
                           job.ecv_down)


def _diff(a, b) -> int:
    """Positions that differ, plus the difference in length."""
    import numpy as np
    a, b = np.asarray(a, dtype=np.int64), np.asarray(b, dtype=np.int64)
    k = min(len(a), len(b))
    return int(np.count_nonzero(a[:k] != b[:k])) + abs(len(a) - len(b))


def compare(got: plainref.Answer, ref: plainref.Answer) -> dict:
    """Each compared number: how far an answer is from the reference's."""
    import numpy as np
    out = {"seq": _diff(got.seq, ref.seq),
           "parent": _diff(got.parent, ref.parent),
           "pst": _diff(got.pst, ref.pst), "sizes": 0, "ecv_down": 0}
    for p in ref.sizes:
        a, b = got.sizes.get(p, np.zeros(0, np.int64)), ref.sizes[p]
        k = max(len(a), len(b))
        out["sizes"] += int(np.abs(np.pad(a, (0, k - len(a)))
                                   - np.pad(b, (0, k - len(b)))).sum())
        out["ecv_down"] += abs(got.ecv_down.get(p, -1) - ref.ecv_down[p])
    return out


@dataclass
class Observed:
    """What the metric readers read from a traced window."""
    tree_records: list       # flight-recorder records, one list per call
    partition_records: list
    profile: dict | None     # tracereduce.summarize of the window
    memory_peak_bytes: int | None
    #: the peak once the graph was made on the device, before any job
    generator_peak_bytes: int | None = None


def _memory_peak(devices) -> int | None:
    peaks_ = [(d.memory_stats() or {}).get("peak_bytes_in_use")
              for d in devices]
    peaks_ = [p for p in peaks_ if p is not None]
    return max(peaks_) if peaks_ else None


def _say(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


@dataclass
class Session:
    """A process set up to run one cell: the program found, the chips
    checked, compilations counted."""
    root: str
    cell: Cell
    devices: list
    compiles: list


def start(root: str, workload: str, devices_for) -> Session:
    cell = load_cell(root, workload)
    cache = os.path.join(root, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    os.environ.pop("SHEEP_TRACE", None)
    if root not in sys.path:
        sys.path.insert(1, root)
    try:
        import sheep_tpu
    except ImportError as exc:
        raise RunError(f"no program to drive: {exc}") from exc
    if os.path.realpath(os.path.dirname(sheep_tpu.__file__)) != \
            os.path.realpath(os.path.join(root, "sheep_tpu")):
        raise RunError(f"sheep_tpu comes from {sheep_tpu.__file__}, not "
                       f"from the checkout at {root}")
    from sheep_tpu import native
    from sheep_tpu.cli.common import init_compile_cache
    import jax
    from jax import monitoring
    init_compile_cache()
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = devices_for(int(cell.workload["chips"]))
    if not native.available():
        raise RunError("the native library did not build or load")
    compiles: list[float] = []

    def _on(event: str, secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(secs)
    monitoring.register_event_duration_secs_listener(_on)
    return Session(root, cell, devices, compiles)


@contextlib.contextmanager
def graph(session: Session, seed: int):
    """The cell's graph from ``seed``, made by its configuration's
    generator and written as a .dat into a per-run directory, and a
    Runner for it; the directory goes at the end."""
    cell = session.cell
    work = os.path.join(session.root, ".bench", "work",
                        f"{cell.workload['name']}.{seed}")
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    runner = None
    try:
        tail, head = cell.generator.edges(cell.config, seed)
        dat = os.path.join(work, "graph.dat")
        kron.write_dat(dat, tail, head)
        runner = Runner(dat, work, cell.traffic,
                        int(cell.workload["chips"]))
        yield runner, tail, head
    finally:
        if runner is not None:
            runner.close()
        shutil.rmtree(work, ignore_errors=True)


def reference(session: Session, tail, head,
              control: str | None = None) -> plainref.Answer:
    traffic = session.cell.traffic
    return plainref.solve(tail, head, [int(p) for p in traffic["parts"]],
                          float(traffic.get("balance_factor", 1)),
                          os.path.join(session.root, ".bench", "build"),
                          control)


def run(args, root: str, devices_for) -> dict:
    session = start(root, args.workload, devices_for)
    with graph(session, args.seed) as (runner, tail, head):
        return _measure(args, session, runner, tail, head)


def _measure(args, session: Session, runner: Runner, tail, head) -> dict:
    cell, devices, compiles = session.cell, session.devices, session.compiles
    dev = devices[0]
    generator_peak = _memory_peak(devices)
    warm = runner.job("warm", partition=False)
    if warm.error:  # the window's jobs will show it too
        _say(f"the warm-up graph2tree failed:\n{warm.error}")
    setup_compiles = len(compiles)
    setup_s = AGE0 + time.perf_counter() - T0
    _say(f"set-up {setup_s:.3f}s: {setup_compiles} compiles "
         f"({sum(compiles):.3f}s), warm-up graph2tree {warm.tree_s:.3f}s")

    traced = bool(args.trace)
    prof_dir = os.path.join(runner.work, "profile")
    if traced:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(prof_dir, profiler_options=opts)
    jobs: list[Job] = []
    w0 = time.perf_counter()
    while not jobs or time.perf_counter() - w0 < args.seconds:
        gc.collect()  # between the timed calls, never inside them
        started = time.perf_counter() - w0
        jobs.append(runner.job(f"job{len(jobs)}", traced))
        jobs[-1].started = started
    window_s = time.perf_counter() - w0
    window_compiles = len(compiles) - setup_compiles
    memory_peak = _memory_peak(devices)
    rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    profile = None
    if traced:
        import jax
        jax.profiler.stop_trace()
        profile = tracereduce.summarize(
            tracereduce.flatten(prof_dir, ANNOTATIONS), ANNOTATIONS)
    print(f"bench: setup_s={setup_s:.3f} compiles={setup_compiles} "
          f"window_compiles={window_compiles} window_s={window_s:.3f} "
          f"jobs={len(jobs)} rss_peak_bytes={rss} "
          f"memory_peak_bytes={memory_peak} "
          f"generator_peak_bytes={generator_peak}", flush=True)
    for i, j in enumerate(jobs):
        _say(f"job {i} at +{j.started:.3f}s: graph2tree {j.tree_s:.4f}s, "
             f"partition_tree {j.partition_s:.4f}s")
        if j.error:
            _say(f"job failed:\n{j.error}")

    ok = [j for j in jobs if not j.error]
    result = {"correct": False, "attempted": len(jobs),
              "failed": len(jobs) - len(ok), "metrics": {},
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(devices),
                         "memory_peak_bytes": memory_peak}}
    if traced:
        obs = Observed([j.tree_records for j in ok],
                       [j.partition_records for j in ok], profile,
                       memory_peak, generator_peak)
        for m in cell.per_layer:
            v = load_reader(m["name"])(obs)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v,
                                                "unit": m["unit"]}
        if profile and profile["busy_s"] is not None:
            result["device"]["busy_s"] = profile["busy_s"]
            result["device"]["window_s"] = profile["window_s"]
            result["breakdown"] = {"device_ops": profile["device_ops"],
                                   "idle_gaps": profile["idle_gaps"]}
    else:
        values = {"setup_s": setup_s}
        if ok:
            values["tree_s"] = sum(j.tree_s for j in ok) / len(ok)
            values["partition_s"] = sum(j.partition_s for j in ok) / len(ok)
        for m in cell.e2e:
            if m["name"] in values:
                result["metrics"][m["name"]] = {"value": values[m["name"]],
                                                "unit": m["unit"]}

    # the reference runs once the window is closed and the peak is read
    t = time.perf_counter()
    ref = reference(session, tail, head)
    worst = dict.fromkeys(LIMITS, 0)
    for j in ok:
        for k, v in compare(answer(j), ref).items():
            worst[k] = max(worst[k], v)
    _say(f"reference {time.perf_counter() - t:.3f}s; {len(ok)} of "
         f"{len(jobs)} jobs checked")
    result["correct"] = bool(ok) and len(ok) == len(jobs) and all(
        worst[k] <= LIMITS[k] for k in LIMITS)
    result["checks"] = {k: {"value": worst[k], "limit": LIMITS[k]}
                        for k in LIMITS}
    for k in LIMITS:
        print(f"check {k}: {worst[k]} (limit {LIMITS[k]})", file=sys.stderr)
    sys.stderr.flush()
    return result


def main(argv: list[str] | None = None, root: str = ROOT,
         devices_for=require_tpu) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")
    try:
        result = run(args, root, devices_for)
    except RunError as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
