"""Phase-level wall-clock breakdown of build_graph_hybrid on one size.

Usage: python scripts/hybrid_profile.py LOG_N [HANDOFF_FACTOR]

Prints one JSON line with per-phase seconds for the BEST of
SHEEP_PROFILE_REPS timed runs (default 2) after one untimed compile run;
every rep's total is kept in ``totals`` so window-variance is visible.
Phases: h2d (edge transfer), prep (prepare_links), reduce (chunk rounds
incl. between-chunk syncs), d2h (link fetch tail), native (C++
union-find tail + Forest build).
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from sheep_tpu.utils import rmat_edges


def main() -> None:
    log_n = int(sys.argv[1])
    factor = int(sys.argv[2]) if len(sys.argv) > 2 else 0
    n = 1 << log_n
    from sheep_tpu.cli.common import init_compile_cache
    init_compile_cache()
    import jax
    import jax.numpy as jnp
    from sheep_tpu.ops.build import prepare_links
    from sheep_tpu.core.forest import native_or_none

    platform = jax.devices()[0].platform
    tail, head = rmat_edges(log_n, 8 << log_n, seed=1)
    if not factor:
        factor = 8 if platform == "cpu" else 3

    def one(record: dict | None):
        def mark(key, t0):
            t1 = time.perf_counter()
            if record is not None:
                record[key] = round(t1 - t0, 4)
            return t1

        t0 = time.perf_counter()
        t = jax.device_put(jnp.asarray(tail, jnp.int32))
        h = jax.device_put(jnp.asarray(head, jnp.int32))
        jnp.max(t[:1]).block_until_ready()
        t0 = mark("h2d", t0)
        seq, _, m, lo, hi, pst = prepare_links(t, h, n)
        int(jnp.max(lo[:1]) + jnp.max(hi[:1]))  # scalar fetch: sync
        t0 = mark("prep", t0)
        # THE production reduce+tail (ops.build.reduce_and_finish_native
        # — shared with build_graph_hybrid so this profile and the
        # on-chip A/Bs measure exactly what the hybrid ships: the
        # streaming windowed handoff by default, the serial fetch + the
        # speculative snapshot when SHEEP_STREAM_HANDOFF=0).  With the
        # stream, the old d2h/native phases merge into one overlapped
        # tail: d2h reports fetch_tail_s minus the fold, native reports
        # the fold, and the per-window breakdown rides along verbatim.
        from sheep_tpu.ops.build import (handoff_input_ok,
                                         reduce_and_finish_native,
                                         fetch_links_host)
        perf: dict = {}
        res = reduce_and_finish_native(
            lo, hi, n, stop_live=factor * n,
            handoff_input=handoff_input_ok(),
            pst_h=lambda: np.asarray(pst).astype(np.uint32),
            accumulate_pst_ok=True, perf=perf)
        rounds, live = res[4], int(res[3])
        if record is not None:
            record["rounds"] = rounds
            record["live"] = live
            record["converged"] = res[0] == "device"
            # rounds == 0: the immediate-handoff skip fired and `live`
            # is the sentinel-inclusive input length, NOT a post-round
            # live count — don't compare it against older records
            record["immediate_handoff"] = rounds == 0 and res[0] != "device"
            record["reduce"] = perf.get("loop_s")
            # packing mode + stream/overlap counters + actual handed-off
            # link count ride along so A/B arms are auditable from the
            # artifact alone
            record.update({k: v for k, v in perf.items()
                           if k in ("overlap", "packed_handoff",
                                    "handoff_links", "stream_mode",
                                    "fetch_windows", "window_fetch_s",
                                    "window_fold_s", "overlap_s",
                                    "overlap_frac", "fold_s")
                           or k.startswith("spec_")})
        if res[0] == "device":  # converged: links already form the forest
            t0 = time.perf_counter()
            lo_h, hi_h, _ = fetch_links_host(res[1], res[2], live, n)
            pst_h = np.asarray(pst).astype(np.uint32)
            t0 = mark("d2h", t0)
            native = native_or_none("auto")
            parent_h, _ = native.build_forest_links(
                lo_h.astype(np.uint32), hi_h.astype(np.uint32), n, pst_h)
            t0 = mark("native", t0)
            return parent_h
        _, parent_h, pst_out, _, _ = res
        if record is not None:
            fold = perf.get("fold_s", 0.0) or 0.0
            record["d2h"] = round(
                max(0.0, perf.get("fetch_tail_s", 0.0) - fold), 4)
            record["native"] = round(fold, 4)
        return parent_h

    one(None)  # compile
    # multiple timed reps (SHEEP_PROFILE_REPS, default 2): single-shot
    # A/B deltas are weakly attributable; the record keeps every rep's
    # total and reports the best rep's phase breakdown
    reps = max(1, int(os.environ.get("SHEEP_PROFILE_REPS", "2")))
    from sheep_tpu.utils.envinfo import env_capture
    best_rec = None
    totals = []
    for _ in range(reps):
        rec = {"op": "hybrid_profile", "log_n": log_n, "platform": platform,
               "handoff_factor": factor, "env": env_capture(platform)}
        t0 = time.perf_counter()
        one(rec)
        rec["total"] = round(time.perf_counter() - t0, 4)
        totals.append(rec["total"])
        if best_rec is None or rec["total"] < best_rec["total"]:
            best_rec = rec
    rec = best_rec
    rec["totals"] = totals
    e = len(tail)
    rec["edges_per_sec"] = round(e / rec["total"], 1)
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
