"""Chip smoke: `graph2tree -i -r` on the TPU, checked against the native build.

One process drives everything: a process that has touched JAX holds the
chip, so no phase starts a child that needs it.  The phases run the user's
entry points in-process (``sheep_tpu.cli.graph2tree.main`` with ``-i -r``,
then ``sheep_tpu.cli.partition_tree.main -g ... 2``, the reference's
partition stage) and compare what they write with the host-native C++ build
(``core.forest.build_forest``):

  hep-th  data/hep-th.dat must print the reference's published goldens
          (TREEFAQS and the 2-part ECV(down) 521 with sizes 3409/4201).
  orkut   an R-MAT graph at orkut's published scale (n = 2^22 vertex
          slots, 117,185,083 edges; BASELINE.md) made from ``--seed``,
          written once as a .dat under ``--out``.  Sequence, parent and
          pst must be bit-identical to the native build, and ECV(down)
          and the part sizes equal.

``--chips 4`` runs only the orkut phase, over a 4-device mesh (the
reference's MPI ``-i -r``), and also requires the edge shards to sit on 4
distinct devices.  Without a TPU the script exits non-zero before any
phase and prints no result.  The last stdout line is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import re
import sys
import time

#: orkut as the reference published it (BASELINE.md:29): 3,072,441
#: vertices and 117,185,083 edges; 2^22 vid slots hold the vertices
ORKUT_LOG_N = 22
ORKUT_EDGES = 117_185_083

#: the reference's published hep-th goldens (.claude/skills/verify)
HEP_TREEFAQS = {"width": 24, "roots": 581, "vheight": 754, "eheight": 2330,
                "verts": 7610, "edges": 15751, "halo": 3532, "core": 0,
                "fill": 0}
HEP_ECV_DOWN = 521
HEP_SIZES = (3409, 4201)

PARTS = 2

#: edge records per block of the streamed evaluator
EVAL_BLOCK = 1 << 24


class SmokeFailure(Exception):
    """A phase produced a wrong or unverifiable result."""


def _say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


@contextlib.contextmanager
def _env(**values: str):
    """Set environment variables for the duration, then restore them."""
    prior = {k: os.environ.get(k) for k in values}
    os.environ.update(values)
    try:
        yield
    finally:
        for k, v in prior.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


@contextlib.contextmanager
def _traced(path: str):
    """Point the repo's flight recorder (SHEEP_TRACE) at ``path`` for the
    duration, then close it so the file is complete."""
    from sheep_tpu.obs import trace as obs
    try:
        with _env(**{obs.ENV: path}):
            yield
    finally:
        obs.close_recorder()


def _run_cli(module, argv: list[str]) -> str:
    """Run one CLI entry point in this process; echo and return stdout."""
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = module.main(argv)
    out = buf.getvalue()
    for line in out.splitlines():
        _say(f"  | {line}")
    if rc:
        raise SmokeFailure(f"{module.__name__} {' '.join(argv)} -> rc {rc}")
    return out


def _parse_faqs(out: str) -> dict:
    block = out[out.index("TREEFAQS:"):]
    return {k: int(v) for k, v in re.findall(r"(\w+):(\d+)", block)
            if k in HEP_TREEFAQS}


def _parse_partition(out: str) -> dict:
    m = re.search(r"First two partition sizes: (\d+) and (\d+)", out)
    e = re.search(r"ECV\(down\): (\d+)", out)
    if not (m and e):
        raise SmokeFailure("partition_tree printed no sizes / ECV(down)")
    return {"sizes": (int(m.group(1)), int(m.group(2))),
            "ecv_down": int(e.group(1))}


def run_pipeline(graph: str, work: str, workers: int) -> dict:
    """``graph2tree -i -r -s SEQ -o TRE -f`` over ``workers`` devices, then
    ``partition_tree -g GRAPH SEQ TRE 2``.  Returns what they printed and
    wrote, the trace events of the build, and the phase wall times.

    ``partition_tree`` runs its streamed O(n)-memory evaluator
    (SHEEP_EVAL_STREAM=1, the path it takes by itself past 2^27
    records): the in-memory one peaks near 320 bytes per edge, which at
    orkut scale exceeds a one-chip host's 40 GiB."""
    from sheep_tpu.cli import graph2tree, partition_tree
    from sheep_tpu.io.seqfile import read_sequence
    from sheep_tpu.io.trefile import read_tree
    from sheep_tpu.obs.trace import read_trace

    name = os.path.splitext(os.path.basename(graph))[0]
    seq_f = os.path.join(work, f"{name}.seq")
    tre_f = os.path.join(work, f"{name}.tre")
    trace_f = os.path.join(work, f"{name}.trace")
    for f in (seq_f, tre_f, trace_f):
        if os.path.exists(f):
            os.unlink(f)
    with _env(SHEEP_WORKERS=str(workers)):
        t0 = time.perf_counter()
        with _traced(trace_f):
            build_out = _run_cli(graph2tree, [graph, "-i", "-r", "-s", seq_f,
                                              "-o", tre_f, "-f"])
        t1 = time.perf_counter()
    with _env(SHEEP_EVAL_STREAM="1"):
        part_out = _run_cli(partition_tree,
                            ["-g", graph, seq_f, tre_f, str(PARTS)])
    t2 = time.perf_counter()
    records, _, _ = read_trace(trace_f)
    events: dict[str, list[dict]] = {}
    spans: dict[str, float] = {}
    for r in records:
        if r.get("k") == "ev":
            events.setdefault(r["name"], []).append(r.get("a", {}))
        elif r.get("k") == "span":
            spans[r["name"]] = spans.get(r["name"], 0.0) + r["dur"]
    parent, pst = read_tree(tre_f)
    return {"seq": read_sequence(seq_f), "parent": parent, "pst": pst,
            "faqs": _parse_faqs(build_out), **_parse_partition(part_out),
            "events": events, "spans": spans,
            "graph2tree_s": t1 - t0, "partition_tree_s": t2 - t1}


def native_oracle(tail, head) -> dict:
    """The host-native reference: degree sequence, C++ union-find forest,
    FFD partition and (streamed) evaluation of the same edge records."""
    from sheep_tpu.core.forest import build_forest
    from sheep_tpu.core.sequence import degree_sequence, sequence_positions
    from sheep_tpu.partition.evaluate import evaluate_partition_streamed
    from sheep_tpu.partition.partition import Partition

    t0 = time.perf_counter()
    max_vid = int(max(tail.max(), head.max()))
    seq = degree_sequence(tail, head)
    forest = build_forest(tail, head, seq, max_vid=max_vid, impl="native")
    t1 = time.perf_counter()
    part = Partition.from_forest(seq, forest, PARTS, max_vid=max_vid)

    def blocks():
        return ((tail[a:a + EVAL_BLOCK], head[a:a + EVAL_BLOCK])
                for a in range(0, len(tail), EVAL_BLOCK))

    report = evaluate_partition_streamed(
        part.parts, blocks, sequence_positions(seq, max_vid), PARTS,
        len(tail))
    return {"seq": seq, "parent": forest.parent, "pst": forest.pst_weight,
            "sizes": (int((part.parts == 0).sum()),
                      int((part.parts == 1).sum())),
            "ecv_down": int(report.ecv_down),
            "build_s": t1 - t0, "partition_s": time.perf_counter() - t1}


def compare(got: dict, want: dict) -> list[str]:
    """Mismatches between a device run and the native oracle."""
    import numpy as np
    bad = [k for k in ("seq", "parent", "pst")
           if not np.array_equal(np.asarray(got[k]), np.asarray(want[k]))]
    bad += [f"{k}: {got[k]} != native {want[k]}"
            for k in ("sizes", "ecv_down") if got[k] != want[k]]
    return bad


def _report_build(got: dict, workers: int) -> None:
    """Print what the build reports about its device path, and fail on a
    fallback or on edge shards that did not spread over the mesh."""
    ev = got["events"]
    spans = got.get("spans", {})
    if workers == 1:
        hand = ev.get("hybrid.handoff")
        if not hand:
            raise SmokeFailure("the hybrid build left no hybrid.handoff "
                               "event: the device path did not run")
        h = hand[-1]
        _say(f"  device reduce rounds: {h.get('rounds')}, live links at "
             f"handoff: {h.get('live')} (folded {h.get('handoff_links')}), "
             f"stream_mode: {h.get('stream_mode')}, kind: {h.get('kind')}; "
             f"reduce loop {spans.get('reduce.loop')}s, handoff fetch "
             f"{spans.get('handoff.fetch')}s, fold "
             f"{spans.get('handoff.fold')}s (host clock)")
        # a handoff that reached the native fold took the streamed
        # windowed tail; any other mode is a path the chip should not take
        if h.get("kind") == "forest" and h.get("stream_mode") != "windowed":
            raise SmokeFailure(f"handoff took {h.get('stream_mode')!r}, "
                               f"not the windowed stream")
    else:
        stage = ev.get("mesh.stage")
        build = ev.get("mesh.build")
        if not stage or not build:
            raise SmokeFailure("the mesh build left no mesh.stage / "
                               "mesh.build event")
        s, b = stage[-1], build[-1]
        _say(f"  edge shards on {s.get('devices')} distinct devices "
             f"(mesh of {s.get('workers')}); reduce rounds "
             f"{b.get('reduce_rounds')}, prep {b.get('prep_s'):.3f}s, "
             f"reduce {b.get('reduce_s'):.3f}s")
        if s.get("devices") != workers:
            raise SmokeFailure(f"edge shards sit on {s.get('devices')} "
                               f"devices, not {workers}")
    chunks = ev.get("reduce.chunk", [])
    _say(f"  reduce chunks resolved: {len(chunks)}")


def phase_hep_th(repo: str, work: str) -> dict:
    """data/hep-th.dat through the device path: the published goldens,
    and bit-identity with the native build."""
    from sheep_tpu.io.edges import load_edges

    graph = os.path.join(repo, "data", "hep-th.dat")
    got = run_pipeline(graph, work, workers=1)
    _report_build(got, 1)
    problems = []
    if got["faqs"] != HEP_TREEFAQS:
        problems.append(f"TREEFAQS {got['faqs']} != golden {HEP_TREEFAQS}")
    if got["ecv_down"] != HEP_ECV_DOWN or got["sizes"] != HEP_SIZES:
        problems.append(f"ECV(down) {got['ecv_down']} sizes {got['sizes']}"
                        f" != golden {HEP_ECV_DOWN} {HEP_SIZES}")
    edges = load_edges(graph)
    problems += compare(got, native_oracle(edges.tail, edges.head))
    if problems:
        raise SmokeFailure("hep-th: " + "; ".join(problems))
    return got


def make_rmat_dat(work: str, log_n: int, num_edges: int, seed: int):
    """R-MAT edges from ``seed``, written once as a .dat under ``work``.
    Returns (path, tail, head)."""
    from sheep_tpu.io.edges import write_dat
    from sheep_tpu.utils import rmat_edges

    tail, head = rmat_edges(log_n, num_edges, seed=seed)
    path = os.path.join(work, f"rmat_{log_n}_{num_edges}_{seed}.dat")
    if not os.path.exists(path):
        write_dat(path, tail, head)
    return path, tail, head


def phase_rmat(work: str, log_n: int, num_edges: int, seed: int,
               workers: int) -> dict:
    """An R-MAT graph through ``graph2tree -i -r`` on ``workers`` devices,
    bit-identical to the native build."""
    t0 = time.perf_counter()
    graph, tail, head = make_rmat_dat(work, log_n, num_edges, seed)
    gen_s = time.perf_counter() - t0
    _say(f"  generated and wrote {num_edges} edges in {gen_s:.3f}s")
    got = run_pipeline(graph, work, workers)
    _report_build(got, workers)
    want = native_oracle(tail, head)
    _say(f"  native oracle: build {want['build_s']:.3f}s, partition+eval "
         f"{want['partition_s']:.3f}s; ECV(down) {want['ecv_down']}, "
         f"sizes {want['sizes']}")
    problems = compare(got, want)
    if problems:
        raise SmokeFailure(f"rmat 2^{log_n}: " + "; ".join(problems))
    got["gen_s"] = gen_s
    return got


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the orkut-scale phase, over a 4-chip mesh")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", default=None,
                    help="work directory (default: .chip_smoke/ in the "
                         "checkout)")
    args = ap.parse_args(argv)

    repo = os.path.dirname(os.path.abspath(__file__))
    t_start = time.perf_counter()
    # a copy of this script outside a checkout has no program to drive
    import sheep_tpu
    if os.path.dirname(os.path.abspath(sheep_tpu.__file__)) != \
            os.path.join(repo, "sheep_tpu"):
        print(f"chip_smoke: sheep_tpu comes from {sheep_tpu.__file__}, "
              f"not from this checkout ({repo})", file=sys.stderr)
        return 1
    from sheep_tpu import native
    from sheep_tpu.cli.common import init_compile_cache
    cache = init_compile_cache()
    import jax
    from jax import monitoring

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU: JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 1
    env_cache = "JAX_COMPILATION_CACHE_DIR" in os.environ
    _say(f"device {dev.platform} {dev.device_kind} x{len(devices)}; "
         f"compile cache {cache} ({'from' if env_cache else 'no'} "
         f"JAX_COMPILATION_CACHE_DIR)")

    compile_s: list[float] = []

    def _on_duration(event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s.append(secs)

    monitoring.register_event_duration_secs_listener(_on_duration)

    if not native.available():
        print("chip_smoke: the native library did not build or load",
              file=sys.stderr)
        return 1
    _say(f"native library loaded: {os.path.basename(native.so_path())}")

    work = os.path.abspath(args.out or os.path.join(repo, ".chip_smoke"))
    os.makedirs(work, exist_ok=True)
    phases = []
    if args.chips == 1:
        phases.append(("hep-th", lambda: phase_hep_th(repo, work)))
    phases.append((f"orkut-scale rmat 2^{ORKUT_LOG_N} x {ORKUT_EDGES}",
                   lambda: phase_rmat(work, ORKUT_LOG_N, ORKUT_EDGES,
                                      args.seed, args.chips)))
    for name, run in phases:
        _say(f"phase {name} ({args.chips} chip{'s' * (args.chips > 1)})")
        c0, t0 = len(compile_s), time.perf_counter()
        try:
            got = run()
        except SmokeFailure as exc:
            print(f"chip_smoke: FAILED {name}: {exc}", file=sys.stderr)
            return 1
        _say(f"phase {name} ok in {time.perf_counter() - t0:.3f}s: "
             f"graph2tree {got['graph2tree_s']:.3f}s, partition_tree "
             f"{got['partition_tree_s']:.3f}s; {len(compile_s) - c0} "
             f"compiles took {sum(compile_s[c0:]):.3f}s")
    _say(f"all phases ok in {time.perf_counter() - t_start:.3f}s "
         f"({len(compile_s)} compiles, {sum(compile_s):.3f}s)")
    print(json.dumps({"ok": True,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
