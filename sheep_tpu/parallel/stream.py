"""Mesh-sharded out-of-core streaming: OOM processing composed with the
device mesh.

The reference's OOM regime runs more partial loads than cores
(scripts/horizontal-dist.sh:22-24, data/oom/) — the graph fits no single
worker, so edge slices stream through while the associative merge stitches
them.  The multi-chip analog here: each host-DRAM edge block is itself
sharded over the 'workers' mesh axis, every worker maps its shard over the
shared sequence, the carry forest (replicated, two length-n arrays) re-enters
as links, the per-worker partial forests all_gather + rebuild associatively
(the per-block equivalent of the reference's mpi_merge custom op,
lib/jnode.cpp:203-250), and pst accumulates by psum.  Device-resident state
stays O(n + block/W) per worker for any edge count.

Like the in-jit merge in parallel.build, the while_loop fixpoint per block
is a correctness twin: the PRODUCTION mesh streaming path is
parallel.chunked.build_graph_streaming_chunked (bounded dispatches only —
one program's device time then grows with the data-dependent round
count), and on one chip the hosted chunked driver (ops.stream
build_graph_streaming_hosted) is the single-device production path.  Both
twins are pinned equal by tests on random multigraphs and 2-process meshes.
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .. import INVALID_JNID
from ..core.forest import Forest
from ..ops.forest import forest_fixpoint, links_from_parent
from ..ops.stream import _full_vid_pos
from jax import shard_map
from .build import _gather_merge, _links_from_positions, _stage, _fetch
from .mesh import AXIS, make_mesh


@functools.partial(jax.jit, static_argnames=("n", "mesh"))
def stream_block_step_sharded(parent: jnp.ndarray, pst: jnp.ndarray,
                              tail: jnp.ndarray, head: jnp.ndarray,
                              pos: jnp.ndarray, n: int, mesh):
    """Fold one mesh-sharded edge block into the replicated carry forest.

    parent int32, pst uint32 [n] replicated (uint32 so the running
    accumulation honors the package-wide uint32 weight contract instead of
    wrapping negative at 2^31); tail/head int32 [B] sharded over
    'workers' (pad with values >= len(pos)-1); pos the _full_vid_pos table.
    Returns (parent, pst, rounds) replicated.
    """
    def body(parent, pst, t, h, posr):
        vid_cap = jnp.int32(posr.shape[0] - 1)
        blo, bhi, pst_local = _links_from_positions(
            posr[jnp.minimum(t, vid_cap)], posr[jnp.minimum(h, vid_cap)], n)
        # carry forest re-enters as its own links on every worker
        clo, chi = links_from_parent(parent, n)
        p_local, _ = forest_fixpoint(jnp.concatenate([clo, blo]),
                                     jnp.concatenate([chi, bhi]), n)
        # per-block associative merge of the partial forests (mpi_merge)
        new_parent, rounds = _gather_merge(p_local, n)
        # per-block delta is int32-safe (a block holds < 2^31 edges); the
        # running carry is uint32 so cumulative counts follow the uint32
        # weight contract rather than wrapping negative at 2^31
        return (new_parent,
                pst + lax.psum(pst_local, AXIS).astype(jnp.uint32),
                rounds)

    fn = shard_map(body, mesh=mesh,
                   in_specs=(P(), P(), P(AXIS), P(AXIS), P()),
                   out_specs=(P(), P(), P()),
                   check_vma=False)
    return fn(parent, pst, tail, head, pos)


def build_graph_streaming_sharded(blocks, n: int, pos: np.ndarray,
                                  block_edges: int,
                                  num_workers: int | None = None):
    """OOM streaming over the mesh: same contract as
    ops.stream.build_graph_streaming, with every block sharded over the
    'workers' axis.  Returns (Forest over n positions, total_rounds).
    """
    mesh = make_mesh(num_workers)
    w = mesh.size
    block_pad = max(w, ((block_edges + w - 1) // w) * w)
    pos_d = _stage(_full_vid_pos(pos, n), mesh, P())
    vid_pad = len(pos)  # pad records map to the table's sentinel slot

    # staged replicated so the step is multi-process safe; the step's
    # replicated outputs feed back in as global arrays directly
    parent = _stage(np.full(n, n, dtype=np.int32), mesh, P())
    pst = _stage(np.zeros(n, dtype=np.uint32), mesh, P())
    round_counts = []
    for tail, head in blocks:
        b = len(tail)
        t = np.full(block_pad, vid_pad, dtype=np.int32)
        h = np.full(block_pad, vid_pad, dtype=np.int32)
        t[:b] = tail
        h[:b] = head
        parent, pst, rounds = stream_block_step_sharded(
            parent, pst, _stage(t, mesh, P(AXIS)), _stage(h, mesh, P(AXIS)),
            pos_d, n, mesh)
        round_counts.append(rounds)
    total_rounds = int(sum(int(_fetch(r)) for r in round_counts)) \
        if round_counts else 0
    parent_np = _fetch(parent).astype(np.int64)
    out = np.full(n, INVALID_JNID, dtype=np.uint32)
    live = parent_np < n
    out[live] = parent_np[live].astype(np.uint32)
    return Forest(out, np.asarray(_fetch(pst), dtype=np.uint32)), total_rounds
