"""Benchmark: device elimination-forest build throughput (edges/sec).

Prints ONE JSON line on stdout.  The metric is end-to-end edges/sec of
the single-chip build (degree histogram + (degree,vid) sort + edge links
+ forest fixpoint + pst) on an R-MAT power-law graph — the analog of the
reference's load-free sort+map phases.  ``vs_baseline`` compares against
the reference's best aggregate MPI throughput on twitter-2010:
1,468,364,884 edges / 18.7 s map = 78.5M edges/s across 18 ranks
(BASELINE.md, data/slurm-twitter/slurm-25.avg:15).

Each size runs in its OWN subprocess (``--one``) so a fault at one size
cannot erase the others; per-size records stream to stderr as they
complete, and the final stdout line is the largest passing size,
annotated with the whole sweep and the first faulting size.  The parent
never imports JAX: a process that has touched JAX holds the chip, and
the children need it.  A child that finds no TPU fails; a sweep with no
record exits non-zero and prints no number.

Env: SHEEP_BENCH_SIZES (csv of log2 sizes; default "16,18,20,22,23"),
SHEEP_BENCH_LOG_N (single size override), SHEEP_BENCH_PATHS (csv subset
of "hybrid,device,host"; default hybrid+host), SHEEP_BENCH_EDGE_FACTOR
(default 8), SHEEP_BENCH_REPS (default 3), SHEEP_BENCH_TIMEOUT (seconds
per size, default 1500).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time


_BASELINE_EDGES_PER_SEC = 1_468_364_884 / 18.7  # twitter map, 18 MPI ranks


def last_record(stdout) -> dict | None:
    """Newest parseable JSON line — children stream partial records
    after each measured path, so a timeout/crash mid-size still
    yields whatever completed."""
    if isinstance(stdout, bytes):
        stdout = stdout.decode(errors="replace")
    for line in reversed((stdout or "").strip().splitlines()):
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        if isinstance(rec, dict) and "edges_per_sec" in rec:
            return rec
    return None


def run_sweep(sizes, run_child, timeout_s: int,
              checkpoint=lambda sweep: None):
    """Walk the sizes through ``run_child`` and collect per-size records.

    ``run_child(log_n) -> (stdout, stderr, returncode, fault_kind|None)``
    is injected (subprocess in production, fakes in tests, so its fault
    semantics are unit-tested).  Returns (sweep,
    first_fault); the sweep ends at the first fault EXCEPT a timeout
    whose child already streamed a headline record — that size is kept
    (marked partial) and the sweep continues (round-4 lesson: the first
    TPU window's whole sweep died at 2^16 because the pure-device path's
    per-slice compiles outlived the budget after the hybrid number was
    already in).  ``checkpoint(sweep)`` is called after every appended
    record so a killed parent still leaves the sizes that finished.
    """
    sweep: list[dict] = []
    first_fault: dict | None = None
    for log_n in sizes:
        rec = None
        stdout, stderr, rc_child, fault_kind = run_child(log_n)
        if fault_kind is not None:
            if stderr:
                sys.stderr.write(stderr)
            print(f"bench: n=2^{log_n} {fault_kind.upper()} "
                  f"after {timeout_s}s", file=sys.stderr)
            rec = last_record(stdout)
            if fault_kind == "timeout" and rec is not None:
                rec["partial"] = True
                sweep.append(rec)
                checkpoint(sweep)
                print(f"bench: n=2^{log_n} -> "
                      f"{rec['edges_per_sec']:.0f} edges/s "
                      f"(headline path done; secondary cut)",
                      file=sys.stderr)
                continue
            first_fault = {"log_n": log_n, "error": fault_kind}
        else:
            sys.stderr.write(stderr)
            rec = last_record(stdout)
            if rc_child != 0:
                err = (stderr or "").strip().splitlines()
                first_fault = {"log_n": log_n,
                               "error": err[-1][:300] if err else "crash"}
                print(f"bench: n=2^{log_n} FAULT rc={rc_child}",
                      file=sys.stderr)
            elif rec is None:
                first_fault = {"log_n": log_n,
                               "error": "unparseable child output"}
                print(f"bench: n=2^{log_n} produced no record",
                      file=sys.stderr)
        if rec is not None:
            if first_fault is not None:
                rec["partial"] = True  # some paths of this size were lost
            sweep.append(rec)
            print(f"bench: n=2^{log_n} -> "
                  f"{rec['edges_per_sec']:.0f} edges/s "
                  f"({rec['rounds']} rounds, best {rec['best_s']}s)",
                  file=sys.stderr)
            checkpoint(sweep)
        if first_fault is not None:
            break
    return sweep, first_fault


def _wanted_paths() -> list[str]:
    """Validated SHEEP_BENCH_PATHS (csv subset of hybrid,device,host).

    Unset defaults to hybrid+host: the pure-device path compiles one
    program per power-of-two slice shape, which can eat a per-size
    budget for a secondary number.  Called in main() BEFORE any child
    starts, so an explicit-value typo fails in under a second.
    """
    raw = os.environ.get("SHEEP_BENCH_PATHS", "")
    if not raw.strip():
        return ["hybrid", "host"]
    wanted = [p.strip() for p in raw.split(",") if p.strip()]
    known = {"hybrid", "device", "host"}
    if set(wanted) - known or not set(wanted) & {"hybrid", "device"}:
        print(f"bench: SHEEP_BENCH_PATHS={','.join(wanted)!r} must be a "
              f"subset of {sorted(known)} and include hybrid or device",
              file=sys.stderr)
        sys.exit(2)
    return wanted


def _run_one(log_n: int) -> dict:
    """Measure one size in this process; returns the result record.

    Two paths are timed end-to-end:
      device — prepare_links + the chunked hosted fixpoint, everything on
               the accelerator (parent materializes on device);
      hybrid — the flagship graph2tree pipeline: device reduction rounds
               kill ~90% of links, the host C++ union-find finishes
               (ops/build.py build_graph_hybrid).  Includes the transfer.
    The headline number is the faster of the two (both are full builds of
    the same bit-identical forest).
    """
    from sheep_tpu.cli.common import init_compile_cache
    init_compile_cache()
    import jax
    import jax.numpy as jnp
    from sheep_tpu.ops import (build_graph_hybrid, forest_fixpoint_hosted,
                               prepare_links)
    from sheep_tpu.utils import rmat_edges

    platform = jax.devices()[0].platform
    if platform != "tpu":
        # a measurement that finds no chip fails: it never reports a
        # host-backend time as a device number
        print(f"bench: no TPU (JAX found {platform!r}); refusing to "
              f"measure", file=sys.stderr)
        sys.exit(3)
    factor = int(os.environ.get("SHEEP_BENCH_EDGE_FACTOR", "8"))
    reps = int(os.environ.get("SHEEP_BENCH_REPS", "3"))
    n = 1 << log_n
    e = factor * n

    print(f"bench: platform={platform} n=2^{log_n} edges={e}", file=sys.stderr)
    tail, head = rmat_edges(log_n, e, seed=1)
    t0 = time.perf_counter()
    t = jax.device_put(jnp.asarray(tail, jnp.int32))
    h = jax.device_put(jnp.asarray(head, jnp.int32))
    t.block_until_ready(), h.block_until_ready()
    h2d_s = time.perf_counter() - t0  # one-time edge upload (load phase)

    def device_build(perf=None):
        seq, pos, m, lo, hi, pst = prepare_links(t, h, n)
        parent, rounds = forest_fixpoint_hosted(lo, hi, n)
        # dispatch is asynchronous: force completion with a scalar
        # fetch that depends on the whole parent array
        return int(jnp.max(parent)), rounds

    def hybrid_build(perf=None):
        # edges are device-resident (t, h) before the clock starts, same
        # as device_build: the reference's 78.5M edges/s baseline is the
        # MAP phase with the graph already in each rank's RAM (load and
        # sort are separate lines in data/slurm-twitter/slurm-25.avg) —
        # while the timed region here still includes the degree sort AND
        # the device->host fetch of the finished tree.  The one-time edge
        # upload is reported separately as ``h2d_s``.  After any real
        # load phase the edges are resident in host RAM as well as HBM;
        # the host copy lets the hybrid recompute seq/pst host-side
        # (bit-identical) instead of fetching 2n*4B from the device
        return build_graph_hybrid(t, h, n, host_edges=(tail, head),
                                  perf=perf)

    from sheep_tpu.utils.envinfo import env_capture
    rec = {"log_n": log_n, "edges": e, "platform": platform,
           "device_kind": jax.devices()[0].device_kind,
           "device_count": len(jax.devices()),
           "h2d_s": round(h2d_s, 4), "env": env_capture(platform)}

    wanted = _wanted_paths()

    # hybrid first: it is the faster path, so if the per-size timeout cuts
    # the slower pure-device measurement short, the partial record printed
    # below still carries the headline-capable number (the parent parses
    # the LAST stdout line).
    for name, fn in (("hybrid", hybrid_build), ("device", device_build)):
        if name not in wanted:
            continue
        out = fn()  # warmup / compile (all chunk shapes)
        times = []
        perfs = []
        for _ in range(reps):
            p: dict = {}
            t0 = time.perf_counter()
            fn(p)
            times.append(time.perf_counter() - t0)
            perfs.append(p)
        best = min(times)
        rec[name] = {"best_s": round(best, 4),
                     "times": [round(x, 4) for x in times],
                     "edges_per_sec": round(e / best, 1)}
        # overlap/pipeline observability for on-chip interpretation: the
        # best rep's reduce+tail breakdown — the streaming windowed
        # handoff's per-window fetch/fold timers and overlap fraction
        # (reduce_and_finish_native), plus the legacy speculation
        # counters when the serial path ran
        best_perf = perfs[times.index(best)]
        if best_perf:
            rec[name]["perf"] = {k: v for k, v in best_perf.items()
                                 if k in ("loop_s", "fetch_tail_s",
                                          "overlap", "stream_mode",
                                          "fetch_windows", "fold_s",
                                          "window_fetch_s",
                                          "window_fold_s", "overlap_s",
                                          "overlap_frac",
                                          "handoff_links",
                                          "packed_handoff")
                                 or k.startswith("spec_")}
        if name == "device":
            rec[name]["rounds"] = int(out[1])
        if name == "hybrid":
            # flight-recorder A/B (ISSUE 10): one extra traced rep of
            # the same build — the record carries the measured tracing
            # overhead vs the untraced best, the per-phase rollup (the
            # ONE code path the overlap/fetch/fold splits come from),
            # and the wall reconciliation (top-level span coverage)
            rec[name]["trace_ab"] = _trace_ab(fn, best, log_n)
        print(f"bench: n=2^{log_n} {name}: {e / best:.0f} edges/s "
              f"(best {best:.3f}s)", file=sys.stderr)
        partial = dict(rec)
        _headline(partial)
        print(json.dumps(partial), flush=True)

    # transparency: the pure host-native path (graph2tree's serial build),
    # recorded but never the headline — the headline must exercise the
    # accelerator.  Measured AFTER the accelerator paths so a slow host
    # build can never consume the per-size budget before the headline
    # number has streamed (the round-4 window-1 failure shape).
    from sheep_tpu.core.forest import build_forest, native_or_none
    from sheep_tpu.core.sequence import degree_sequence
    if "host" in wanted and native_or_none("auto") is not None:
        def host_build():  # same scope as device/hybrid: sort + links + UF
            seq_host = degree_sequence(tail, head)
            build_forest(tail, head, seq_host, max_vid=n - 1)

        host_build()  # warmup (page in edge arrays, build the .so)
        host_times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            host_build()
            host_times.append(time.perf_counter() - t0)
        host_s = min(host_times)
        rec["host_native"] = {"best_s": round(host_s, 4),
                              "edges_per_sec": round(e / host_s, 1)}
        # threads_ab (round 14, SHEEP_BENCH_THREADS_AB=1): the same host
        # build under forced SHEEP_NATIVE_THREADS ∈ {1,2,4}, best-of-reps,
        # CRC-asserted bit-identical across T.  The dedicated acceptance
        # record is scripts/threadbench.py (own subprocess per arm); this
        # in-sweep arm rides the existing sizes so a committed BENCH
        # record carries the per-size thread scaling too.
        if os.environ.get("SHEEP_BENCH_THREADS_AB", "") == "1":
            import zlib
            prev_t = os.environ.get("SHEEP_NATIVE_THREADS")
            ab: dict = {}
            crcs = set()
            try:
                for t in (1, 2, 4):
                    os.environ["SHEEP_NATIVE_THREADS"] = str(t)
                    seq_host = degree_sequence(tail, head)
                    f = build_forest(tail, head, seq_host, max_vid=n - 1)
                    crcs.add((zlib.crc32(f.parent.tobytes()),
                              zlib.crc32(f.pst_weight.tobytes())))
                    times = []
                    for _ in range(reps):
                        t0 = time.perf_counter()
                        host_build()
                        times.append(time.perf_counter() - t0)
                    ab[f"t{t}_best_s"] = round(min(times), 4)
            finally:
                if prev_t is None:
                    os.environ.pop("SHEEP_NATIVE_THREADS", None)
                else:
                    os.environ["SHEEP_NATIVE_THREADS"] = prev_t
            ab["crc_identical"] = len(crcs) == 1
            assert ab["crc_identical"], "threads_ab arms diverged"
            rec["host_native"]["threads_ab"] = ab

    _headline(rec)
    # final stream line: the record including host_native (the parent
    # parses the LAST stdout line)
    print(json.dumps(rec), flush=True)
    return rec


def _trace_ab(fn, untraced_best_s: float, log_n: int) -> dict:
    """Run ``fn`` SHEEP_BENCH_REPS times with SHEEP_TRACE on; return the
    A/B record: best traced vs best untraced (best-vs-best — a single
    traced rep against an untraced best would charge run-to-run variance
    to the recorder), the in-memory phase rollup of the best rep, and
    the trace-file reconciliation (sum of top-level span durations vs
    the traced wall — the <=5% acceptance check of ISSUE 10)."""
    import tempfile
    from sheep_tpu.obs import trace as obs_trace

    reps = int(os.environ.get("SHEEP_BENCH_REPS", "3"))
    tdir = tempfile.mkdtemp(prefix="sheep-bench-trace-")
    prev = os.environ.get(obs_trace.ENV)
    times, paths, summaries = [], [], []
    try:
        for i in range(reps):
            tpath = os.path.join(tdir, f"hybrid_{log_n}_{i}.trace")
            os.environ[obs_trace.ENV] = tpath
            t0 = time.perf_counter()
            fn({})
            times.append(time.perf_counter() - t0)
            paths.append(tpath)
            summaries.append(obs_trace.trace_summary())
            obs_trace.close_recorder()
    finally:
        if prev is None:
            os.environ.pop(obs_trace.ENV, None)
        else:
            os.environ[obs_trace.ENV] = prev
    best_i = times.index(min(times))
    traced_s = times[best_i]
    out = {
        "traced_best_s": round(traced_s, 4),
        "traced_times": [round(x, 4) for x in times],
        "untraced_best_s": round(untraced_best_s, 4),
        "overhead_frac": round(traced_s / untraced_best_s - 1.0, 4)
        if untraced_best_s > 0 else 0.0,
        "summary": summaries[best_i],
    }
    try:
        records, _, _ = obs_trace.read_trace(paths[best_i], "repair")
        top = sum(float(r.get("dur", 0.0)) for r in records
                  if r.get("k") == "span" and r.get("par") is None)
        out["top_level_span_s"] = round(top, 4)
        out["wall_recon_frac"] = round(top / traced_s, 4) \
            if traced_s > 0 else 0.0
    except Exception as exc:  # a failed read must not sink the bench
        out["trace_read_error"] = f"{type(exc).__name__}: {exc}"
    return out


def _headline(rec: dict) -> None:
    """Fill the headline fields from whichever accelerator paths exist."""
    paths = [k for k in ("device", "hybrid") if k in rec]
    top = max(paths, key=lambda k: rec[k]["edges_per_sec"])
    rec["path"] = top
    rec["rounds"] = rec.get("device", {}).get("rounds", 0)
    rec["best_s"] = rec[top]["best_s"]
    rec["edges_per_sec"] = rec[top]["edges_per_sec"]
    rec["vs_baseline"] = round(
        rec[top]["edges_per_sec"] / _BASELINE_EDGES_PER_SEC, 4)


def main() -> None:
    _wanted_paths()  # fail fast on a config typo, before any child starts
    if len(sys.argv) > 2 and sys.argv[1] == "--one":
        # the per-path stream inside _run_one already printed the final
        # record; printing it again would just duplicate the line
        _run_one(int(sys.argv[2]))
        return

    factor = int(os.environ.get("SHEEP_BENCH_EDGE_FACTOR", "8"))
    if os.environ.get("SHEEP_BENCH_LOG_N"):
        sizes = [int(os.environ["SHEEP_BENCH_LOG_N"])]
    else:
        sizes = [int(s) for s in
                 os.environ.get("SHEEP_BENCH_SIZES",
                                "16,18,20,22,23").split(",")]
    timeout_s = int(os.environ.get("SHEEP_BENCH_TIMEOUT", "1500"))

    progress_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "bench_progress.json")
    try:
        os.unlink(progress_path)  # never leave a stale sidecar looking live
    except OSError:
        pass

    def run_child(log_n: int):
        """Returns (stdout, stderr, returncode, fault_kind|None)."""
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__),
                 "--one", str(log_n)],
                capture_output=True, timeout=timeout_s)
        except subprocess.TimeoutExpired as exc:
            return (exc.stdout or b"").decode(errors="replace"), \
                (exc.stderr or b"").decode(errors="replace"), None, \
                "timeout"
        return (proc.stdout.decode(errors="replace"),
                proc.stderr.decode(errors="replace"), proc.returncode,
                None)

    def _checkpoint(sweep: list[dict]) -> None:
        # the sidecar survives the benchmark being killed mid-sweep
        try:
            with open(progress_path, "w") as f:
                json.dump({"sweep": sweep}, f)
        except OSError:
            pass

    sweep, first_fault = run_sweep(sizes, run_child, timeout_s, _checkpoint)
    if not sweep:
        print(f"bench: no size produced a record: {first_fault}",
              file=sys.stderr)
        sys.exit(1)
    top = max(sweep, key=lambda r: r["log_n"])
    out = {
        "metric": (f"device_build_edges_per_sec_rmat_n2^{top['log_n']}"
                   f"_e{factor}x"),
        "value": top["edges_per_sec"],
        "unit": "edges/sec",
        "vs_baseline": top["vs_baseline"],
        "sweep": [{k: r[k] for k in
                   ("log_n", "edges_per_sec", "rounds", "best_s", "path",
                    "h2d_s", "partial", "hybrid", "device", "host_native",
                    "env")
                   if k in r}
                  for r in sweep],
    }
    if first_fault is not None:
        out["first_fault"] = first_fault
    print(json.dumps(out))


if __name__ == "__main__":
    main()
