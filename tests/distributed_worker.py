"""Worker process for the 2-process jax.distributed test (test_parallel.py).

Each process joins the coordination service via
sheep_tpu.parallel.init_distributed (the reference's `mpiexec` analog,
SURVEY §5: multi-host over DCN), then runs the distributed degree sort over
a global mesh spanning both processes' devices and writes its result.

Usage: python distributed_worker.py COORD_ADDR NUM_PROCS PROC_ID OUT_DIR
"""

from __future__ import annotations

import os
import sys


def main() -> None:
    coord, num, pid, out_dir = (sys.argv[1], int(sys.argv[2]),
                                int(sys.argv[3]), sys.argv[4])
    mode = sys.argv[5] if len(sys.argv) > 5 else "degree"
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    # Bound the coordinator join: a dead/misaddressed coordinator must
    # fail this worker with a clear error (parallel/mesh.init_distributed)
    # instead of hanging until the pytest-level subprocess timeout.
    os.environ.setdefault("SHEEP_CONNECT_TIMEOUT", "120")
    if mode in ("build", "stream", "chunked", "chunked_stream"):
        return main_build(coord, num, pid, out_dir, mode)

    import numpy as np

    import jax

    from sheep_tpu.parallel import init_distributed
    init_distributed(coordinator_address=coord, num_processes=num,
                     process_id=pid)
    assert jax.process_count() == num, jax.process_count()
    # The global device view must span every process (DCN-analog mesh).
    assert len(jax.devices()) == num * jax.local_device_count()

    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    # Distributed degree sort (lib/sequence.h:65-93): every process owns an
    # edge-disjoint shard, histograms are psum'd across the whole mesh, and
    # every process computes the identical sequence.
    from sheep_tpu.utils import rmat_edges
    n = 1 << 8
    tail, head = rmat_edges(8, 4 * n, seed=23)

    mesh = Mesh(np.array(jax.devices()), ("workers",))
    w = mesh.size
    e_pad = ((len(tail) + w - 1) // w) * w
    t = np.full(e_pad, 0, dtype=np.int32)
    h = np.full(e_pad, 0, dtype=np.int32)
    t[: len(tail)] = tail
    h[: len(head)] = head

    # Build the globally-sharded arrays from per-process shards.
    shard = NamedSharding(mesh, P("workers"))
    tg = jax.make_array_from_process_local_data(shard, t[
        pid * (e_pad // num): (pid + 1) * (e_pad // num)], (e_pad,))
    hg = jax.make_array_from_process_local_data(shard, h[
        pid * (e_pad // num): (pid + 1) * (e_pad // num)], (e_pad,))

    from jax import lax

    from jax import shard_map

    def body(ts, hs):
        local = jnp.zeros(n, jnp.int32).at[ts].add(1).at[hs].add(1)
        return lax.psum(local, "workers")

    deg = shard_map(body, mesh=mesh, in_specs=(P("workers"), P("workers")),
                    out_specs=P())(tg, hg)
    # out_specs=P() replicates the result: every process can read its own
    # addressable shard.  Padding used vid 0; subtract its extra counts.
    deg_local = np.asarray(deg.addressable_shards[0].data).copy()
    deg_local[0] -= 2 * (e_pad - len(tail))

    want = np.bincount(tail, minlength=n) + np.bincount(head, minlength=n)
    np.testing.assert_array_equal(deg_local, want)

    with open(os.path.join(out_dir, f"ok.{pid}"), "w") as f:
        f.write("ok")


def main_build(coord: str, num: int, pid: int, out_dir: str,
               mode: str) -> None:
    """Cross-process pipelines over a mesh spanning both processes
    (global-array staging via parallel.build._stage), checked against the
    sequential oracle: 'build' = the full `-i -r` path, 'stream' = OOM
    block streaming composed with the mesh."""
    import jax

    from sheep_tpu.parallel import init_distributed
    init_distributed(coordinator_address=coord, num_processes=num,
                     process_id=pid)
    assert jax.process_count() == num, jax.process_count()

    import numpy as np

    from sheep_tpu.core.forest import build_forest
    from sheep_tpu.core.sequence import degree_sequence
    from sheep_tpu.utils import rmat_edges

    tail, head = rmat_edges(9, 4 << 9, seed=31)
    want_seq = degree_sequence(tail, head)
    want = build_forest(tail, head, want_seq)
    if mode == "build":
        from sheep_tpu.parallel.build import build_graph_distributed
        seq, forest = build_graph_distributed(tail, head)
        np.testing.assert_array_equal(seq, want_seq)
        np.testing.assert_array_equal(forest.parent, want.parent)
        np.testing.assert_array_equal(forest.pst_weight, want.pst_weight)
    elif mode == "chunked":
        # the bounded-dispatch production shape across a 2-process mesh:
        # host chunk loop + stats fetches must be multi-process safe
        from sheep_tpu.parallel import build_graph_chunked_distributed
        seq, forest = build_graph_chunked_distributed(tail, head)
        np.testing.assert_array_equal(seq, want_seq)
        np.testing.assert_array_equal(forest.parent, want.parent)
        np.testing.assert_array_equal(forest.pst_weight, want.pst_weight)
    elif mode == "chunked_stream":
        from sheep_tpu.core.sequence import sequence_positions
        from sheep_tpu.parallel import build_graph_streaming_chunked
        n = int(max(tail.max(), head.max())) + 1
        n = max(n, len(want_seq))
        pos = sequence_positions(want_seq, n - 1)
        block = len(tail) // 3 + 1
        forest, _ = build_graph_streaming_chunked(
            ((tail[a:a + block], head[a:a + block])
             for a in range(0, len(tail), block)),
            n, pos, block_edges=block)
        m = len(want_seq)
        np.testing.assert_array_equal(forest.parent[:m], want.parent)
        np.testing.assert_array_equal(forest.pst_weight[:m],
                                      want.pst_weight)
    else:
        from sheep_tpu.core.sequence import sequence_positions
        from sheep_tpu.parallel import build_graph_streaming_sharded
        n = int(max(tail.max(), head.max())) + 1
        n = max(n, len(want_seq))
        pos = sequence_positions(want_seq, n - 1)
        block = len(tail) // 3 + 1
        forest, _ = build_graph_streaming_sharded(
            ((tail[a:a + block], head[a:a + block])
             for a in range(0, len(tail), block)),
            n, pos, block_edges=block)
        m = len(want_seq)
        np.testing.assert_array_equal(forest.parent[:m], want.parent)
        np.testing.assert_array_equal(forest.pst_weight[:m],
                                      want.pst_weight)

    with open(os.path.join(out_dir, f"ok.{pid}"), "w") as f:
        f.write("ok")


if __name__ == "__main__":
    main()
