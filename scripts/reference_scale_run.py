"""Reference-scale run: a twitter-2010-sized build on one host core.

The reference's headline rows (BASELINE.md) are twitter-2010 —
41.65M vertices / 1.468B edges — loaded+sorted+mapped across up to 24
MPI ranks (best map 18.7s at 18 ranks = 78.5M edges/s aggregate,
4.4M edges/s per rank).  There is no network egress in this container,
so the graph is an R-MAT stand-in at the same edge count:
n = 2^25 (33.6M) x factor 44 = 1,476,395,008 records (+0.5% vs twitter).

Pipeline, phases timed with the reference's grammar:
  1. synthesize the .dat once (cached in /tmp, 17.7GB)
  2. streamed degree sequence — O(n) resident (fileSequence analog)
  3. load + native map: edge records -> links -> exact counting-sorted
     union-find build (the reference's map phase, single core)
  4. facts on the forest
  5. FFD partition (2 and 18 parts) + streamed O(n)-memory evaluation

Emits REFSCALE_r03.json at the repo root.  Runs entirely on the host
(jax is only imported transitively, never used).

Usage: python scripts/reference_scale_run.py [log_n] [factor] [parts]
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

_TWITTER_E = 1_468_364_884
_TWITTER_MAP_18RANK_S = 18.7


def main() -> None:
    log_n = int(sys.argv[1]) if len(sys.argv) > 1 else 25
    factor = int(sys.argv[2]) if len(sys.argv) > 2 else 44
    parts_big = int(sys.argv[3]) if len(sys.argv) > 3 else 18
    records = factor << log_n

    path = f"/tmp/refscale_{log_n}_{factor}.dat"
    rec: dict = {"log_n": log_n, "edge_factor": factor, "records": records,
                 "twitter_records": _TWITTER_E}

    if not os.path.exists(path) or os.path.getsize(path) != 12 * records:
        from sheep_tpu.cli.make_graph import main as make_graph
        t0 = time.time()
        assert make_graph([str(log_n), str(factor), path, "1"]) == 0
        rec["generate_s"] = round(time.time() - t0, 1)
        print(f"generated {path} in {rec['generate_s']}s", flush=True)

    # 2. streamed degree sequence (bounded memory)
    from sheep_tpu.cli.degree_sequence import _streamed_sequence
    t0 = time.time()
    seq = _streamed_sequence(path)
    rec["sort_s"] = round(time.time() - t0, 2)
    print(f"Sorted in: {rec['sort_s']} seconds", flush=True)

    # 3. map.  Default: whole-graph load + one native pass (the
    # reference's in-RAM map).  SHEEP_REFSCALE_STREAM=1 instead runs the
    # bounded-memory carry-fold (core.build_forest_streaming, the
    # data/oom analog): O(n + block) resident, never holding the 11.8GB
    # edge arrays — the load phase disappears into the stream.
    from sheep_tpu.core.forest import native_or_none
    from sheep_tpu.core.sequence import sequence_positions
    native = native_or_none("auto")
    assert native is not None, "native runtime required at this scale"
    max_vid = int(seq.max()) if len(seq) else 0
    if os.environ.get("SHEEP_REFSCALE_STREAM", "") == "1":
        from sheep_tpu.core.forest import build_forest_streaming
        from sheep_tpu.io.edges import iter_dat_blocks

        class _El:  # the partition/eval tail only needs max_vid
            pass
        el = _El()
        el.max_vid = max_vid
        rec["load_s"] = 0.0
        rec["oom_stream"] = True
        print("Loaded graph in: 0.0 seconds", flush=True)
        t0 = time.time()
        forest = build_forest_streaming(
            iter_dat_blocks(path, 1 << 24), seq, max_vid=max_vid)
        pos = sequence_positions(seq, max_vid)
    else:
        from sheep_tpu.io.edges import read_dat
        t0 = time.time()
        el = read_dat(path)
        rec["load_s"] = round(time.time() - t0, 2)
        print(f"Loaded graph in: {rec['load_s']} seconds", flush=True)
        t0 = time.time()
        pos = sequence_positions(seq, el.max_vid)
        lo, hi = native.edges_to_links(el.tail, el.head, pos)
        parent, pst = native.build_forest_links(lo, hi, len(seq))
        from sheep_tpu.core.forest import Forest
        forest = Forest(parent, pst)
        del lo, hi
    rec["map_s"] = round(time.time() - t0, 2)
    rec["edges_per_sec_native"] = round(records / rec["map_s"], 1)
    rec["vs_twitter_map_aggregate"] = round(
        rec["edges_per_sec_native"] / (_TWITTER_E / _TWITTER_MAP_18RANK_S), 4)
    rec["vs_twitter_map_per_rank"] = round(
        rec["edges_per_sec_native"] / (_TWITTER_E / _TWITTER_MAP_18RANK_S / 18),
        3)
    print(f"Mapped in: {rec['map_s']} seconds "
          f"({rec['edges_per_sec_native']:.0f} edges/s)", flush=True)

    # 4. facts
    from sheep_tpu.core.facts import compute_facts
    t0 = time.time()
    facts = compute_facts(forest)
    rec["facts_s"] = round(time.time() - t0, 2)
    rec["tree"] = {"width": int(facts.width), "roots": int(facts.root_cnt),
                   "verts": int(facts.vert_cnt), "edges": int(facts.edge_cnt)}
    facts.print()

    # 5. partition + streamed evaluation
    from sheep_tpu.io.edges import iter_dat_blocks
    from sheep_tpu.partition import Partition
    from sheep_tpu.partition.evaluate import evaluate_partition_streamed
    for np_ in (2, parts_big):
        t0 = time.time()
        part = Partition.from_forest(seq, forest, np_, max_vid=el.max_vid)
        p_s = round(time.time() - t0, 2)
        print(f"Partitioned in: {p_s} seconds", flush=True)
        t0 = time.time()
        ev = evaluate_partition_streamed(
            part.parts, lambda: iter_dat_blocks(path, 1 << 24), pos, np_,
            file_edges=records)
        e_s = round(time.time() - t0, 2)
        ev.print()
        rec[f"parts{np_}"] = {
            "partition_s": p_s, "eval_s": e_s,
            "ecv_down": int(ev.ecv_down),
            "ecv_down_frac": round(ev.ecv_down / records, 6)}

    name = "REFSCALE_OOM_r03.json" if rec.get("oom_stream") \
        else "REFSCALE_r03.json"
    out = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), name)
    with open(out, "w") as f:
        json.dump(rec, f, indent=1)
    print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
