"""End-to-end scale run: the BASELINE-config-5 shaped proof (>=100M edges).

Pipeline (reference anchors: data/oom/twitter-c1.avg, scripts/
horizontal-dist.sh OOM mode):
  1. synthesize an R-MAT .dat via the make_graph CLI (one-time, cached)
  2. streamed degree sequence (host, O(n) resident — fileSequence analog)
  3. streamed forest build on the device: 16M-edge blocks folded through
     the hosted chunked reducer, carry compacted between blocks
  4. facts + EXACT validation against the native whole-graph oracle
     (this host has RAM for the oracle; the streamed path never uses it)
  5. native FFD partition + O(n)-memory streamed ECV evaluation

Emits the reference's phase-line grammar plus one final JSON record, also
written to SCALE_r04.json at the repo root when the run is at artifact
scale (>= 100M records; smaller validation runs only print).

Usage: python scripts/scale_run.py [log_n] [edge_factor] [parts]
Defaults: 2^23 vertices x 16 = 134M records, 8 parts.
Env: SHEEP_SCALE_SKIP_ORACLE=1 skips step 4's full-graph rebuild;
SHEEP_SCALE_BLOCK overrides the 16M-record streamed block size (lets a
time-budgeted on-chip run exercise MANY carry folds + a partial final
block without a multi-GB transfer).
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

#: records per streamed block (default 16M; SHEEP_SCALE_BLOCK overrides)
_BLOCK = int(os.environ.get("SHEEP_SCALE_BLOCK", str(1 << 24)))


def _stream_impl() -> str | None:
    """SHEEP_SCALE_STREAM override: "native" / "device" / "both" / unset."""
    which = os.environ.get("SHEEP_SCALE_STREAM", "") or None
    if which not in (None, "native", "device", "both"):
        raise SystemExit(f"SHEEP_SCALE_STREAM={which!r}: expected "
                         "'native', 'device', or 'both'")
    return which


def main() -> None:
    log_n = int(sys.argv[1]) if len(sys.argv) > 1 else 23
    factor = int(sys.argv[2]) if len(sys.argv) > 2 else 16
    parts = int(sys.argv[3]) if len(sys.argv) > 3 else 8
    records = factor << log_n

    from sheep_tpu.cli.common import init_compile_cache
    init_compile_cache()
    import jax

    path = f"/tmp/scale_{log_n}_{factor}.dat"
    if not os.path.exists(path) or \
            os.path.getsize(path) != 12 * records:
        from sheep_tpu.cli.make_graph import main as make_graph
        t0 = time.time()
        assert make_graph([str(log_n), str(factor), path, "1"]) == 0
        print(f"Loaded graph in: {time.time() - t0:f} seconds")

    platform = jax.devices()[0].platform
    rec: dict = {"log_n": log_n, "edge_factor": factor, "records": records,
                 "parts": parts, "platform": platform, "block": _BLOCK}
    print(f"scale_run: platform={platform} records={records:,}",
          file=sys.stderr)

    # --- streamed sequence (sort phase) ---
    from sheep_tpu.cli.degree_sequence import _streamed_sequence
    from sheep_tpu.core.sequence import sequence_positions
    t0 = time.time()
    seq = _streamed_sequence(path)
    sort_s = time.time() - t0
    print(f"Sorted in: {sort_s:f} seconds")
    rec["sort_s"] = round(sort_s, 2)
    n = len(seq)
    max_vid = int(seq.max()) if n else 0
    pos = sequence_positions(seq, max_vid).astype(np.int64)

    # --- streamed forest build (map+reduce phases fused) ---
    # Two streamed implementations share the carry-fold design: the native
    # union-find fold (the host OOM production path — data/oom analog) and
    # the device chunked-reducer fold (the accelerator path).  Default:
    # native on the cpu backend, device on accelerators; SHEEP_SCALE_STREAM
    # overrides with "native"/"device"/"both".
    from sheep_tpu.io.edges import iter_dat_blocks
    which = _stream_impl() or ("native" if platform == "cpu" else "device")
    if which in ("native", "both"):
        from sheep_tpu.core.forest import build_forest_streaming
        t0 = time.time()
        forest = build_forest_streaming(
            iter_dat_blocks(path, _BLOCK), seq, max_vid=max_vid)
        map_s = time.time() - t0
        rec["map_native_stream_s"] = round(map_s, 2)
        rec["edges_per_sec_stream_native"] = round(records / map_s, 1)
        rounds = 0
    if which in ("device", "both"):
        from sheep_tpu.ops import build_graph_streaming_hosted
        t0 = time.time()
        forest_d, rounds = build_graph_streaming_hosted(
            iter_dat_blocks(path, _BLOCK), n, pos, _BLOCK)
        map_s = time.time() - t0
        rec["fixpoint_rounds"] = rounds
        rec["edges_per_sec_stream_device"] = round(records / map_s, 1)
        if which == "both":
            m = len(seq)
            np.testing.assert_array_equal(forest_d.parent[:m],
                                          forest.parent[:m])
        else:
            forest = forest_d
    print(f"Mapped in: {map_s:f} seconds")
    print(f"Reduced in: 0.000000 seconds")  # fused into the block folds
    rec["map_s"] = round(map_s, 2)
    rec["edges_per_sec_stream"] = round(records / map_s, 1)

    from sheep_tpu.core.facts import compute_facts
    facts = compute_facts(forest)
    facts.print()
    rec["tree"] = {"width": int(facts.width), "roots": int(facts.root_cnt),
                   "verts": int(facts.vert_cnt), "edges": int(facts.edge_cnt)}

    # --- exact oracle validation (native whole-graph build) ---
    if os.environ.get("SHEEP_SCALE_SKIP_ORACLE", "") != "1":
        from sheep_tpu.core.forest import build_forest
        from sheep_tpu.io.edges import load_edges
        t0 = time.time()
        edges = load_edges(path)
        oracle = build_forest(edges.tail, edges.head, seq,
                              max_vid=edges.max_vid, impl="native")
        oracle_s = time.time() - t0
        del edges
        np.testing.assert_array_equal(forest.parent, oracle.parent)
        np.testing.assert_array_equal(forest.pst_weight, oracle.pst_weight)
        print(f"scale_run: streamed forest == native oracle "
              f"(oracle {oracle_s:.1f}s)", file=sys.stderr)
        rec["oracle_s"] = round(oracle_s, 2)
        rec["oracle_equal"] = True
        rec["edges_per_sec_native"] = round(records / oracle_s, 1)

    # --- partition + streamed evaluation ---
    from sheep_tpu.partition import Partition
    from sheep_tpu.partition.evaluate import evaluate_partition_streamed
    t0 = time.time()
    part = Partition.from_forest(seq, forest, parts, max_vid=max_vid)
    part_s = time.time() - t0
    print(f"Partitioned in: {part_s:f} seconds")
    rec["partition_s"] = round(part_s, 2)
    part.print()
    t0 = time.time()
    report = evaluate_partition_streamed(
        part.parts, lambda: iter_dat_blocks(path, _BLOCK), pos, parts,
        records)
    eval_s = time.time() - t0
    report.print()
    rec["eval_s"] = round(eval_s, 2)
    rec["ecv_down"] = report.ecv_down
    rec["ecv_down_frac"] = round(report.ecv_down / records, 6)

    # Only a BASELINE-config-5-shaped run (>=100M records) replaces the
    # round artifact — small validation invocations must not clobber it.
    if records >= 100_000_000:
        out = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "SCALE_r04.json")
        with open(out, "w") as f:
            f.write(json.dumps(rec) + "\n")
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
