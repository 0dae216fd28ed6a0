"""Workers-vs-throughput curve for the chunked mesh build (MESHBENCH).

Per worker count, A/Bs the two chunked mesh drivers on one R-MAT size:
``unified`` (global-f rounds from round 1, the production default — its
edges_per_sec is each row's headline) vs ``split`` (map-then-reduce, the
reference's transportable-partials shape), each with prep/map/reduce
phase seconds and round counts nested per variant.  The baseline being
chased is itself an 18-rank aggregate
(data/slurm-twitter/slurm-25.avg:13-17), so the aggregate-scaling story
needs measured per-worker-count numbers, not arithmetic.

On the CPU backend this runs the virtual 8-device mesh (set by this
script; the 1-core bench host shares one core across virtual workers, so
absolute speedup is not expected there — the curve demonstrates how round
counts, collective costs, and phase splits scale with W, and becomes a
true throughput curve the moment a multi-chip window exists).  On an
accelerator backend it uses however many real devices exist.

Round 5 adds the collective-volume model per variant (the VERDICT r04
item-4 evidence): per-worker logical payload bytes and a ring-model
wire-bytes estimate, plus ``collective_reduction_vs_nogather`` — the
gather-tail's cut vs the round-4 all-rounds-pmin shape.

Round 6 adds the SHARDED tail (SHEEP_MESH_TAIL_SHARD, the VERDICT r05
item-3 fix: the round-5 tail was replicated, so W-1 chips re-derived the
identical plateau collapse) and its per-chip work model: ``unified`` now
runs the sharded tail, ``unified_noshard`` is the round-5 replicated
shape, and each arm carries ``tail_per_chip_link_rounds`` — live links
times rounds actually processed per chip in its tail (window share *
local rounds + replicated finish) — the column the item-3 gate reads:
it must fall with W under the shard and is constant in W without it.

Honesty note: on the VIRTUAL mesh any arm's ``total_s`` at W>1 reads
slower because one core computes every worker's share serially (and the
replicated tail W times); on real hardware the sharded local rounds are
parallel wall-time while each avoided pmin round saves a real dispatch
+ all-reduce.  The bytes/rounds/per-chip-work columns are exact on both.

Usage: python scripts/mesh_bench.py [log_n] [edge_factor] [workers_csv]
Defaults: 2^18, 8, "1,2,4,8".  Writes MESHBENCH_r06.json at the repo root
when run at the default size or larger (smaller runs only print).
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main() -> None:
    log_n = int(sys.argv[1]) if len(sys.argv) > 1 else 18
    factor = int(sys.argv[2]) if len(sys.argv) > 2 else 8
    workers = [int(w) for w in (sys.argv[3] if len(sys.argv) > 3
                                else "1,2,4,8").split(",")]
    reps = int(os.environ.get("SHEEP_MESHBENCH_REPS", "3"))

    # a CPU backend gets the virtual 8-device mesh; must be set before jax
    # touches backends, mirroring tests/conftest.py
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    from sheep_tpu.cli.common import init_compile_cache
    init_compile_cache()
    import jax

    platform = jax.devices()[0].platform
    ndev = len(jax.devices())
    workers = [w for w in workers if w <= ndev]

    from sheep_tpu.parallel.chunked import (build_links_chunked_sharded,
                                            stage_edges_2d)
    from sheep_tpu.parallel.mesh import make_mesh
    from sheep_tpu.utils import rmat_edges

    from sheep_tpu.utils.envinfo import env_capture

    n = 1 << log_n
    e = factor << log_n
    tail, head = rmat_edges(log_n, e, seed=1)
    rec = {"log_n": log_n, "edges": e, "platform": platform,
           "devices": ndev, "reps": reps, "env": env_capture(platform),
           "curve": []}
    print(f"mesh_bench: platform={platform} ndev={ndev} n=2^{log_n} "
          f"edges={e}", file=sys.stderr)

    for w in workers:
        mesh = make_mesh(w)
        t2d, h2d = stage_edges_2d(tail, head, n, mesh)
        jax.block_until_ready((t2d, h2d))
        row = {"workers": w}
        # unified (gather-tail + sharded tail, the round-6 production
        # path) / unified_noshard (round-5: gather-tail, replicated
        # tail) / unified_nogather (round-4 all-rounds-pmin, the comm
        # model's baseline) / split (the reference's transportable-
        # partials shape)
        # gather_tail/tail_shard pinned explicitly on every unified arm:
        # inherited SHEEP_MESH_GATHER_TAIL=0 / SHEEP_MESH_TAIL_SHARD=0
        # would otherwise silently collapse the comparison arms
        variants = (("unified", True, True, True),
                    ("unified_noshard", True, True, False),
                    ("unified_nogather", True, False, False),
                    ("split", False, None, None))
        for label, unified, gt, tsh in variants:
            best = None
            for _ in range(reps + 1):  # +1 warmup/compile
                tm = {}
                comm: dict = {}
                t0 = time.perf_counter()
                _, _, _, parent, _ = build_links_chunked_sharded(
                    t2d, h2d, n, mesh, timings=tm, unified=unified,
                    gather_tail=gt, tail_shard=tsh, comm=comm)
                total = time.perf_counter() - t0
                tm["total_s"] = total
                tm["comm"] = comm
                if best is None or total < best["total_s"]:
                    best = tm
            comm = best["comm"]
            # collective-volume model (VERDICT r04 item 4): per-worker
            # logical payload, plus the ring-allreduce wire model
            # (aggregate bytes over all W links: 2(W-1) x payload per
            # all-reduce; all_gather delivers (W-1) x shard to each of
            # W workers)
            payload = comm.get("pmin_payload_bytes", 0) \
                + comm.get("gather_payload_bytes", 0)
            wire = 2 * (w - 1) * comm.get("pmin_payload_bytes", 0) \
                + (w - 1) * comm.get("gather_payload_bytes", 0)
            # modeled collective seconds on real ICI: wire bytes spread
            # over W ring links of SHEEP_ICI_GBPS each (default 45 GB/s
            # per link — v5e-class ICI; an ASSUMPTION, labeled as such,
            # for the compute-normalized story VERDICT r04 item 3 asks
            # for) plus a per-collective dispatch floor
            ici_gbps = float(os.environ.get("SHEEP_ICI_GBPS", "45"))
            n_gathers = 0
            if comm.get("gather_payload_bytes", 0):
                n_gathers = 2 if comm.get("tail_shard_rounds", 0) else 1
            n_colls = comm.get("sharded_global_rounds", 0) + n_gathers
            coll_s = wire / (max(w, 1) * ici_gbps * 1e9) \
                + n_colls * 5e-6
            # per-chip tail work (links x rounds actually processed per
            # chip): sharded = this chip's window share through the
            # local rounds + the (replicated, small) finish; replicated
            # = every chip grinds the whole gathered set every round
            gather_live = comm.get("tail_gather_live", 0)
            if comm.get("tail_shard_rounds", 0) > 0:
                row_live = comm.get("tail_shard_row_live") or [0]
                per_chip_tail = (max(row_live)
                                 * comm.get("tail_shard_rounds", 0)
                                 + comm.get("tail_finish_live", 0)
                                 * comm.get("tail_rounds", 0))
            else:
                per_chip_tail = gather_live * comm.get("tail_rounds", 0)
            row[label] = {
                "map_s": round(best["map_s"], 4),
                "reduce_s": round(best["reduce_s"], 4),
                "prep_s": round(best["prep_s"], 4),
                "total_s": round(best["total_s"], 4),
                "map_rounds": best["map_rounds"],
                "reduce_rounds": best["reduce_rounds"],
                "sharded_global_rounds": comm.get("sharded_global_rounds"),
                "tail_rounds": comm.get("tail_rounds"),
                "tail_shard_rounds": comm.get("tail_shard_rounds"),
                "tail_shard_row_live": comm.get("tail_shard_row_live"),
                "tail_gather_live": comm.get("tail_gather_live"),
                "tail_finish_live": comm.get("tail_finish_live"),
                "tail_per_chip_link_rounds": per_chip_tail,
                "pmin_payload_bytes": comm.get("pmin_payload_bytes"),
                "gather_payload_bytes": comm.get("gather_payload_bytes"),
                "collective_payload_bytes": payload,
                "ring_wire_bytes": wire,
                "modeled_collective_s_at_ici": round(coll_s, 6),
                "edges_per_sec": round(e / best["total_s"], 1)}
        row["edges_per_sec"] = row["unified"]["edges_per_sec"]
        base = row["unified_nogather"]["collective_payload_bytes"]
        ours = row["unified"]["collective_payload_bytes"]
        row["collective_reduction_vs_nogather"] = \
            round(base / ours, 2) if ours else None
        # the reference's whole reduce communication: ONE MPI_Reduce of
        # 2 words/vertex (lib/jnode.cpp:228-241) = 8(n+1) payload bytes
        ref_reduce = 8 * (n + 1)
        row["reference_single_reduce_bytes"] = ref_reduce
        row["payload_vs_reference_reduce"] = \
            round(ours / ref_reduce, 2) if ours else None
        rec["curve"].append(row)
        print(f"mesh_bench: W={w} unified "
              f"{row['unified']['total_s']}s "
              f"({row['unified']['sharded_global_rounds']} pmin r + "
              f"{row['unified']['tail_shard_rounds']} shard r + "
              f"{row['unified']['tail_rounds']} tail r, "
              f"per-chip tail "
              f"{row['unified']['tail_per_chip_link_rounds'] / 1e6:.2f}M "
              f"link-rounds vs noshard "
              f"{row['unified_noshard']['tail_per_chip_link_rounds'] / 1e6:.2f}M, "
              f"{ours / 1e6:.1f}MB payload) vs nogather "
              f"{row['unified_nogather']['total_s']}s "
              f"({base / 1e6:.1f}MB) = "
              f"{row['collective_reduction_vs_nogather']}x cut; split "
              f"{row['split']['total_s']}s -> "
              f"{row['edges_per_sec']:.0f} edges/s", file=sys.stderr)

    if log_n >= 18:
        out = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "MESHBENCH_r06.json")
        with open(out, "w") as f:
            f.write(json.dumps(rec) + "\n")
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
