"""Mesh-sharded distributed build: the `-i -r` path as one SPMD program.

Reference semantics being reproduced (SURVEY §3.1):

  - ``-i`` mpiSequence (lib/sequence.h:65-93): per-rank degree histogram,
    MPI_Allreduce(SUM), then every rank sorts the identical histogram.
    Here: per-shard ``bincount`` + ``lax.psum`` + replicated sort.
  - map (lib/jtree.cpp insert loop per rank on its partial graph): here the
    batched forest fixpoint on the local edge shard.
  - ``-r`` mpi_merge (lib/jnode.cpp:203-250, a non-commutative MPI_Reduce
    custom op): the merge is associative over same-sequence partials, so a
    single all_gather of the per-shard (kid, parent) links followed by one
    fixpoint rebuild is equivalent to any reduction-tree order — including
    the reference's binary MPI tree and the file path's REDUCTION=2
    tournament.  pst weights are a plain psum.

Edges are padded to a multiple of the worker count with (n, n) phantom
records: the phantom vid occupies histogram slot n which is sliced away, and
its links map to the kernel sentinel, so padding cannot perturb results.
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import PartitionSpec as P

from .. import INVALID_JNID
from ..core.forest import Forest
from ..ops.forest import forest_fixpoint, pst_weights
from ..ops.sort import degree_order
from .mesh import AXIS, make_mesh


def _links_from_positions(pt, ph, n: int):
    """Shared per-shard link mapping: position pairs -> (lo, hi, pst_local).

    The pst/absent-vid contract (jtree.cpp:47-49): every edge whose
    earlier endpoint is present counts toward pst — including edges to
    absent vids (position >= n), which never insert and stay postorder
    forever; only self-loops/padding/both-absent (lo == hi) are excluded.
    The returned lo/hi are sentinel-masked for the fixpoint, which must
    see only fully-present links.
    """
    sent = jnp.int32(n)
    lo = jnp.minimum(pt, ph)
    hi = jnp.maximum(pt, ph)
    pst_local = pst_weights(jnp.where(lo == hi, sent, lo), n)
    dead = (lo >= hi) | (hi >= sent)
    return jnp.where(dead, sent, lo), jnp.where(dead, sent, hi), pst_local


def _gather_merge(parent_local, n: int):
    """All-gather the per-worker partial forests and rebuild associatively
    (the reference's non-commutative MPI_Reduce custom op,
    lib/jnode.cpp:203-250).  Returns (parent, rounds), replicated."""
    sent = jnp.int32(n)
    parents = lax.all_gather(parent_local, AXIS)  # [W, n]
    kid = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), parents.shape)
    live = parents < sent
    mlo = jnp.where(live, kid, sent).reshape(-1)
    mhi = jnp.where(live, parents, sent).reshape(-1)
    return forest_fixpoint(mlo, mhi, n)


def _sharded_build(tail, head, given_pos, n: int, do_merge: bool = True):
    """Per-shard body; runs under shard_map over the 'workers' axis.

    ``given_pos``: None computes the degree sequence on device (the `-i`
    sort); otherwise a replicated vid->position table is used as-is (the
    `-r`-without-`-i` case, where the sequence comes from a file).
    ``do_merge``: False skips the reduce and returns per-worker partials
    (the `-i`-without-`-r` case, whose trees feed the file-path tournament).
    """
    sent = jnp.int32(n)
    t = tail.astype(jnp.int32)
    h = head.astype(jnp.int32)

    # --- distributed degree sort (mpiSequence) ---
    if given_pos is None:
        deg_local = jnp.zeros(n + 1, jnp.int32).at[t].add(1).at[h].add(1)
        deg = lax.psum(deg_local, AXIS)[:n]
        seq, pos, m = degree_order(deg)  # replicated, identical per worker
    else:
        posi = given_pos.astype(jnp.int32)
        # INVALID (0xFFFFFFFF) slots arrive as -1 after the int32 view.
        absent = (posi < 0) | (posi >= n)
        pos = jnp.where(absent, sent, posi)
        seq = jnp.full(n, sent, jnp.int32)
        vids = jnp.arange(n, dtype=jnp.int32)
        # absent vids scatter out-of-bounds and are dropped
        seq = seq.at[jnp.where(absent, n, pos)].set(vids, mode="drop")
        m = jnp.int32(n) - jnp.sum(absent, dtype=jnp.int32)

    # --- map: local partial forest over the shared sequence ---
    pos_ext = jnp.concatenate([pos, jnp.full((1,), sent, jnp.int32)])
    lo, hi, pst_local = _links_from_positions(pos_ext[t], pos_ext[h], n)
    parent_local, map_rounds = forest_fixpoint(lo, hi, n)

    if not do_merge:
        parents = lax.all_gather(parent_local, AXIS)  # [W, n]
        psts = lax.all_gather(pst_local, AXIS)
        return seq, pos, m, parents, psts, lax.pmax(map_rounds, AXIS)

    # --- reduce: associative merge of the partial forests ---
    # NOTE: this in-jit while_loop fixpoint is fine for the merge's input
    # (<= W*n tree links, most of which are already final), but its
    # device time grows with the data-dependent round count (see
    # ops/forest.py).  The bounded-dispatch production twin is
    # parallel.chunked (map = local chunk rounds, reduce = pmin-combined
    # jump table); this in-jit path remains the single-dispatch
    # correctness twin and the shape the dryrun compiles.
    parent, rounds = _gather_merge(parent_local, n)
    pst = lax.psum(pst_local, AXIS)
    return seq, pos, m, parent, pst, rounds


@functools.partial(jax.jit,
                   static_argnames=("n", "mesh", "with_pos", "do_merge"))
def distributed_build_step(tail: jnp.ndarray, head: jnp.ndarray, n: int, mesh,
                           pos: jnp.ndarray | None = None,
                           with_pos: bool = False, do_merge: bool = True):
    """Jitted SPMD build over `mesh`: edge shards in, replicated forest out.

    tail/head must have length divisible by the mesh size (pad with n).
    Returns (seq, pos, num_active, parent, pst, merge_rounds); ``parent[v]
    == n`` marks roots, everything in full n-slot position space.  With
    ``do_merge=False`` parent/pst come back stacked [W, n] (per-worker
    partials).  ``with_pos`` switches to an externally-given replicated
    vid->position table instead of the on-device degree sort.
    """
    body = functools.partial(_sharded_build, n=n, do_merge=do_merge)
    if with_pos:
        fn = shard_map(
            lambda t, h, p: body(t, h, p),
            mesh=mesh,
            in_specs=(P(AXIS), P(AXIS), P()),
            out_specs=(P(), P(), P(), P(), P(), P()),
            check_vma=False,
        )
        return fn(tail, head, pos)
    fn = shard_map(
        lambda t, h: body(t, h, None),
        mesh=mesh,
        in_specs=(P(AXIS), P(AXIS)),
        out_specs=(P(), P(), P(), P(), P(), P()),
        # The merge fixpoint's while_loop carries worker-varying state, so
        # replication of the (genuinely replicated: same all_gather input on
        # every worker, deterministic compute) outputs can't be statically
        # inferred.
        check_vma=False,
    )
    return fn(tail, head)


def _pad_edges(tail, head, n, w):
    e = len(tail)
    e_pad = max(w, ((e + w - 1) // w) * w)
    t = np.full(e_pad, n, dtype=np.int64)
    h = np.full(e_pad, n, dtype=np.int64)
    t[:e] = tail
    h[:e] = head
    return t.astype(np.int32), h.astype(np.int32)


def _stage(x_np, mesh, spec):
    """Host numpy -> device array under `spec`.  Single-process: a plain
    transfer.  Multi-process (after init_distributed, the mpiexec-across-
    nodes analog): every process holds the full array — the reference's
    shared-filesystem load — and contributes the shards it addresses, so
    the result is one global array spanning the DCN mesh."""
    from jax.sharding import NamedSharding
    sharding = NamedSharding(mesh, spec)
    if jax.process_count() == 1:
        # host memory straight into the shards — no staging copy on the
        # default device first
        return jax.device_put(x_np, sharding)
    return jax.make_array_from_callback(
        x_np.shape, sharding, lambda idx: x_np[idx])


def _fetch(x):
    """Replicated device array -> host numpy, multi-process safe (reads
    this process's addressable copy; out_specs P() replicates)."""
    if isinstance(x, jax.Array) and jax.process_count() > 1:
        return np.asarray(x.addressable_data(0))
    return np.asarray(x)


def _to_forest(parent, pst, n, m):
    # Trim to the m active slots, then reuse the ops converter.  Passing
    # n=m is sound: live parents of active nodes are themselves active
    # positions (< m), and both the root sentinel n and any padding slot
    # value are >= m, so they map to INVALID either way.
    from ..ops.forest import _to_forest as ops_to_forest
    return ops_to_forest(np.asarray(parent)[:m], np.asarray(pst)[:m], m)


def _mesh_kernel() -> str:
    """Which multi-worker kernel the public wrappers route through:
    "chunked" (default — bounded dispatches, the execution shape real
    hardware needs) or "loop" (the single-dispatch while_loop twin —
    fewer host syncs, still the dryrun's compile-coverage shape).
    Anything else is an error: a typo must not silently select the
    kernel that faults on real hardware at scale."""
    import os
    kernel = os.environ.get("SHEEP_MESH_KERNEL", "chunked")
    if kernel not in ("chunked", "loop"):
        raise ValueError(
            f"SHEEP_MESH_KERNEL={kernel!r} must be 'chunked' or 'loop'")
    return kernel


def _run_distributed(tail, head, num_vertices, num_workers, seq, do_merge,
                     mesh=None):
    """Shared prologue + dispatch for the host-facing wrappers.

    Returns (out_seq, parent, pst, n, m, mesh_size) with parent/pst either
    merged [n] or stacked [W, n] depending on ``do_merge``; n == 0 signals
    the empty graph.  ``mesh``: pass an already-built mesh to avoid
    constructing it twice.
    """
    if mesh is None:
        mesh = make_mesh(num_workers)
    n = num_vertices
    if n is None:
        n = int(max(tail.max(initial=0), head.max(initial=0))) + 1 if len(tail) else 0
    if seq is not None and len(seq):
        n = max(n, int(seq.max()) + 1)
    if n == 0:
        return np.empty(0, np.uint32), None, None, 0, 0, mesh.size
    if mesh.size == 1:
        # A 1-worker mesh is a plain whole-graph build (merge of one
        # partial).  Use the chunked hosted kernel: identical results, and
        # it is the execution shape real hardware needs — the in-jit
        # while_loop below faults on long runs there (ops/forest.py).
        # (The merged case normally never reaches here: the public
        # wrapper routes it through the flagship hybrid first.)
        return _single_worker_build(tail, head, n, seq, do_merge)
    t_np, h_np = _pad_edges(tail, head, n, mesh.size)
    t = _stage(t_np, mesh, P(AXIS))
    h = _stage(h_np, mesh, P(AXIS))
    if seq is None:
        dseq, _, m, parent, pst, _ = distributed_build_step(
            t, h, n, mesh, do_merge=do_merge)
        m = int(_fetch(m))
        out_seq = _fetch(dseq)[:m].astype(np.uint32)
    else:
        from ..core.sequence import sequence_positions
        pos = sequence_positions(seq, n - 1)
        pos = _stage(pos.astype(np.int64).astype(np.int32), mesh, P())
        _, _, m, parent, pst, _ = distributed_build_step(
            t, h, n, mesh, pos=pos, with_pos=True, do_merge=do_merge)
        m = len(seq)
        out_seq = np.asarray(seq, dtype=np.uint32)
    return out_seq, _fetch(parent), _fetch(pst), n, m, mesh.size


def _single_worker_build(tail, head, n, seq, do_merge):
    """The mesh-of-one case via the hosted kernel (same output contract)."""
    from ..ops.build import prepare_links
    from ..ops.forest import forest_fixpoint_hosted

    # vids are < n < 2^31: cast straight to int32, no int64 staging copy
    # (two 8-byte staging arrays would cost ~2GB at the 134M-edge scale)
    t = jnp.asarray(np.asarray(tail), jnp.int32)
    h = jnp.asarray(np.asarray(head), jnp.int32)
    if seq is None:
        dseq, pos, m, lo, hi, pst = prepare_links(t, h, n)
        m = int(m)
        out_seq = np.asarray(dseq)[:m].astype(np.uint32)
    else:
        from ..ops.sort import given_seq_links
        lo, hi, pst = given_seq_links(t, h, seq, n)
        m = len(seq)
        out_seq = np.asarray(seq, dtype=np.uint32)
    parent, _ = forest_fixpoint_hosted(lo, hi, n)
    if not do_merge:
        parent = parent[None, :]
        pst = pst[None, :]
    return out_seq, parent, pst, n, m, 1


def _selfcheck_forest(seq, forest, what: str):
    """Integrity tier 3 at the build/merge boundary: run the vectorized
    fast oracle (core.validate.check_forest_fast) on the forest this path
    is about to hand downstream.  O(n) numpy on host — negligible next to
    the build — and it turns a sick-backend wrong answer into a typed
    IntegrityError at the boundary where it happened.  SHEEP_SELFCHECK=0
    opts out (the oracle itself is exercised by tests either way)."""
    import os
    if os.environ.get("SHEEP_SELFCHECK", "1") == "0":
        return seq, forest
    from ..core.validate import check_forest_fast
    from ..integrity.errors import IntegrityError
    from ..obs import trace as obs
    with obs.span("build.selfcheck", n=len(forest.parent)):
        problems = check_forest_fast(forest)
    if problems:
        raise IntegrityError(
            f"{what} produced an invalid forest: " + "; ".join(problems))
    return seq, forest


def build_graph_distributed(tail: np.ndarray, head: np.ndarray,
                            num_vertices: int | None = None,
                            num_workers: int | None = None,
                            seq: np.ndarray | None = None):
    """Host-facing distributed build: (seq uint32 [m], Forest over m).

    ``seq``: an externally-given elimination order (the `-r`-without-`-i`
    case); None runs the device degree sort.  A mesh of one worker routes
    through the flagship hybrid (device reduction + native union-find
    tail — measured ~4x the pure-device path on-chip), which with a given
    ``seq`` also skips the device degree sort entirely.

    SHEEP_CHECKPOINT_DIR (the scripts' restart contract,
    dist-partition.sh -C) reroutes through the fault-tolerant runtime:
    checkpoint/resume at chunk boundaries, retry-with-backoff, and the
    mesh -> single-chip -> host degradation ladder (sheep_tpu.runtime).
    Results are bit-identical; the hybrid/pipelined fast paths are
    traded for survivability.
    """
    import os
    if os.environ.get("SHEEP_CHECKPOINT_DIR"):
        from ..runtime.driver import build_graph_resilient
        return build_graph_resilient(tail, head, num_vertices=num_vertices,
                                     num_workers=num_workers, seq=seq)
    mesh = make_mesh(num_workers)
    if mesh.size == 1 and len(tail):
        from ..ops.build import build_graph_hybrid
        return _selfcheck_forest(
            *build_graph_hybrid(tail, head, num_vertices=num_vertices,
                                seq=seq), what="hybrid build")
    if _mesh_kernel() == "chunked":
        # production default: bounded dispatches only — the in-jit
        # while_loop fixpoint below faults on real hardware once its
        # wall time outgrows the backend's per-execution budget
        # (PERF_NOTES; SHEEP_MESH_KERNEL=loop selects the
        # single-dispatch twin, which stays the dryrun's compile shape)
        from .chunked import build_graph_chunked_distributed
        return _selfcheck_forest(
            *build_graph_chunked_distributed(
                tail, head, num_vertices=num_vertices,
                num_workers=num_workers, seq=seq),
            what="chunked mesh build")
    out_seq, parent, pst, n, m, _ = _run_distributed(
        tail, head, num_vertices, num_workers, seq, do_merge=True, mesh=mesh)
    if n == 0:
        return out_seq, Forest(np.empty(0, np.uint32), np.empty(0, np.uint32))
    return _selfcheck_forest(out_seq, _to_forest(parent, pst, n, m),
                             what="mesh build")


def map_graph_distributed(tail: np.ndarray, head: np.ndarray,
                          num_vertices: int | None = None,
                          num_workers: int | None = None,
                          seq: np.ndarray | None = None):
    """Map-only (`-i` without `-r`): per-worker partial forests, no merge.

    Returns (seq uint32 [m], [Forest over m] * W) — each partial tree covers
    the full vertex set over the shared sequence, ready for the file-path
    merge tournament (reference graph2tree.cpp:148,158 rank-suffixed saves).
    """
    if _mesh_kernel() == "chunked":
        from .chunked import map_graph_chunked_distributed
        return map_graph_chunked_distributed(
            tail, head, num_vertices=num_vertices,
            num_workers=num_workers, seq=seq)
    out_seq, parents, psts, n, m, w = _run_distributed(
        tail, head, num_vertices, num_workers, seq, do_merge=False)
    if n == 0:
        return out_seq, []
    return out_seq, [_to_forest(parents[i], psts[i], n, m) for i in range(w)]
