"""graph2tree: load graph -> sequence -> elimination forest [-> partition].

Flag surface and control flow mirror graph2tree.cpp:44-246; the MPI switches
are re-targeted at the TPU mesh (this is the one deliberate redesign):

  -i / -r   In the reference these pick MPI collectives across ranks
            (mpiSequence / mpi_merge).  Here either switch runs the fused
            SPMD build over a ``jax.sharding.Mesh`` of all local devices in
            ONE process — edge records are sharded over the 'workers' axis,
            the degree sort is a psum'd histogram, and the tree reduce is an
            all_gather + associative rebuild (sheep_tpu.parallel).  The
            worker count is the device count (override: SHEEP_WORKERS).
  -l n/k    partial file load for the multi-process file path (map-worker).

Everything else is host-native: the C++ runtime (sheep_tpu.native) does the
streaming insert and FFD partition exactly like the reference's serial path.
"""

from __future__ import annotations

import getopt
import os
import sys

import numpy as np

from ..core.facts import compute_facts
from ..core.forest import Forest, build_forest
from ..core.sequence import degree_sequence
from ..core.validate import is_valid_forest
from ..io.edges import load_edges
from ..io.seqfile import read_sequence, write_sequence
from ..io.trefile import write_tree
from ..obs import trace as obs
from ..partition.partition import Partition
from .common import PhaseClock, graph_stats, print_phase, print_tree

USAGE = "USAGE: graph2tree input_graph [options ...]"


def _tree_sig(seq) -> str:
    """Input signature stamped into .tre sidecars: identifies the
    (n, sequence) the tree was built over.  Partial trees of one build
    share it, so merge_trees can refuse a cross-build tournament — the
    real compatibility requirement is "same sequence", which edge bytes
    cannot express (each worker sees a different slice)."""
    import numpy as np

    from ..runtime.snapshot import input_signature
    seq = np.asarray(seq, dtype=np.uint32)
    return input_signature(len(seq), seq)


def _make_jopts(make_kids, make_pst, make_jxn, memory_limit, width_limit,
                find_max_width):
    from ..core.jxn import JxnOptions
    return JxnOptions(make_kids=make_kids, make_pst=make_pst,
                      make_jxn=make_jxn,
                      memory_limit=memory_limit or (1 << 30),
                      width_limit=width_limit,
                      find_max_width=find_max_width)


def _finish_sort(seq, use_mesh_sort, sequence_filename, clock,
                 leader=True, writer=True):
    """Write the sequence when `-i -s` asked for it and emit the Sorted
    phase line per the reference grammar (graph2tree.cpp:177-184).
    ``leader``/``writer`` gate the print / shared-fs write in multi-host
    runs (non-leader processes compute the same replicated results)."""
    if use_mesh_sort and sequence_filename and writer:
        write_sequence(seq, sequence_filename)
    if (use_mesh_sort or sequence_filename == "") and leader:
        print_phase("Sorted", clock.phase_seconds())


def main(argv: list[str] | None = None) -> int:
    """The command; one ``cli.graph2tree`` span covers the call, and the
    layers' spans (io.load, prep, reduce.loop, handoff.*, io.write, ...)
    are its direct children."""
    with obs.span("cli.graph2tree"):
        return _main(argv)


def _main(argv: list[str] | None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    from .common import maybe_start_heartbeat
    _hb = maybe_start_heartbeat()  # noqa: F841 — beats while we build
    try:
        # Long options are the fault-tolerance surface (sheep_tpu.runtime):
        # they have no reference counterpart, so they take GNU spellings
        # instead of burning more single-letter flags.
        opts, args = getopt.gnu_getopt(
            argv, "irl:p:s:o:vkejm:w:xfdtc",
            ["checkpoint-dir=", "resume", "max-retries=", "ext",
             "distext"])
    except getopt.GetoptError as exc:
        if (exc.opt or "").startswith(("checkpoint-dir", "max-retries",
                                       "resume", "ext", "distext")):
            print(f"Option --{exc.opt}: {exc.msg}.")
            return 1
        o = (exc.opt or "?")[:1]
        if o in ("s", "o", "l"):
            print(f"Option -{o} requires a string.")
        elif o in ("m", "w", "p"):
            print(f"Option -{o} requires a long long.")
        else:
            print(f"Unknown option character '{o}'.")
        return 1

    use_mesh_sort = use_mesh_reduce = False
    part = num_parts = 0
    partitions = 0
    sequence_filename = ""
    output_filename = ""
    verbose = False
    make_kids = make_pst = make_jxn = False
    memory_limit = 0
    width_limit = 0
    find_max_width = False
    do_faqs = do_print = do_validate = False
    use_ext = False
    use_distext = False

    for o, a in opts:
        if o == "-i":
            use_mesh_sort = not use_mesh_sort
        elif o == "-r":
            use_mesh_reduce = not use_mesh_reduce
        elif o == "-l":
            part_s, num_s = a.split("/")
            part, num_parts = int(part_s), int(num_s)
        elif o == "-p":
            partitions = int(a)
        elif o == "-s":
            sequence_filename = a
        elif o == "-o":
            output_filename = a
        elif o == "-v":
            verbose = not verbose
        elif o == "-k":
            make_kids = not make_kids
        elif o == "-e":
            make_pst = not make_pst
        elif o == "-j":
            make_jxn = not make_jxn
        elif o == "-m":
            memory_limit = int(a) * (1 << 20)
        elif o == "-w":
            width_limit = int(a)
        elif o == "-x":
            find_max_width = not find_max_width
        elif o == "-f":
            do_faqs = not do_faqs
        elif o == "-t":
            do_print = not do_print
        elif o == "-c":
            do_validate = not do_validate
        elif o == "--ext":
            use_ext = True
        elif o == "--distext":
            use_distext = True

    if not args:
        print(USAGE)
        return 1
    graph_filename = args[0]

    from .common import runtime_config_from_opts
    rt_cfg = runtime_config_from_opts(opts)

    clock = PhaseClock()
    use_mesh = use_mesh_sort or use_mesh_reduce
    is_leader = use_mesh or sequence_filename == ""
    proc0 = True  # this process writes shared-filesystem outputs
    if use_mesh:
        # Multi-host launch (the reference's mpiexec across nodes): join
        # the coordination service before any backend work; only process 0
        # is the leader (rank-0 logic, graph2tree.cpp:158-159).  Unlike
        # the reference's per-rank partial writes, every process here
        # computes the full (replicated) result, so non-leaders skip
        # writes entirely rather than racing on the same files.
        from .common import init_compile_cache, maybe_init_distributed
        init_compile_cache()
        if maybe_init_distributed() != 0:
            is_leader = False
            proc0 = False

    jxn_mode = make_kids or make_pst or make_jxn or width_limit or \
        find_max_width

    # External-memory routing (ISSUE 9): decided BEFORE the load — the
    # whole point is that the edge list never enters RAM.  --ext forces;
    # SHEEP_EXT_BLOCK is the env twin; a configured SHEEP_MEM_BUDGET the
    # in-RAM load cannot fit routes automatically.  Only the serial
    # whole-file .dat path streams (mesh/jxn/partial loads keep their
    # in-RAM semantics), and a partitioned-graph copy (-p with -o) still
    # needs the records — say so and fall back instead of surprising.
    if not use_mesh and not jxn_mode and not num_parts \
            and graph_filename.endswith(".dat"):
        # Distributed routing first (ISSUE 13): --distext forces;
        # SHEEP_DISTEXT_LEGS is the env twin; auto when even the ext
        # rung's single-leg stream cannot meet the budget.  The job
        # needs -o (the supervisor exports the final tree there) and no
        # partition request (same limitation as --ext, plus the records
        # live across N legs) — say so and fall back, never surprise.
        if not use_distext:
            from ..ops.distext import should_use_distext
            use_distext = should_use_distext(graph_filename)
        if use_distext and (partitions or not output_filename):
            print("warning: the distributed out-of-core build needs -o "
                  "and cannot partition (the edge records never load in "
                  "one process); falling back to the single-process "
                  "path", file=sys.stderr)
            use_distext = False
        if not use_ext and not use_distext:
            from ..ops.extmem import should_use_extmem
            use_ext = should_use_extmem(graph_filename)
        if use_ext and partitions and output_filename:
            print("warning: the external-memory build cannot write a "
                  "partitioned graph copy (the edge records never load); "
                  "falling back to the in-RAM path", file=sys.stderr)
            use_ext = False
    else:
        use_ext = False
        use_distext = False

    if use_distext:
        # The supervised distributed job (ops/distext.run_distext): the
        # supervisor owns the whole hist -> Allreduce -> map -> merge
        # lifecycle and prints the reference phase grammar itself.  The
        # state dir doubles as the checkpoint surface (--checkpoint-dir
        # redirects it), so a rerun resumes off the fsck'd survivors.
        from ..integrity.errors import IntegrityError
        from ..ops.distext import run_distext
        from ..supervisor import (SupervisionFailed, SupervisorKilled,
                                  SupervisorConfig)
        state_dir = (rt_cfg.checkpoint_dir if rt_cfg is not None else
                     None) or output_filename + ".distext"
        try:
            run_distext(graph_filename, state_dir,
                        SupervisorConfig.from_env(),
                        out_file=output_filename)
        except (SupervisionFailed, SupervisorKilled, IntegrityError,
                OSError) as exc:
            print(f"graph2tree: distext: {exc}", file=sys.stderr)
            return 1
        if verbose:
            print_phase("Finished", clock.total_seconds())
        return 0

    if verbose:
        print(f"Loading {graph_filename}...")
    if use_mesh and num_parts:
        # The reference clobbers -l under MPI: part/num_parts become the
        # rank mapping (graph2tree.cpp:134-143), so every record is still
        # processed across the job.  The mesh path is that whole job in one
        # process; a user-supplied -l cannot be honored — say so instead of
        # silently processing the full graph.
        print(f"warning: -l {part}/{num_parts} is superseded by -i/-r "
              f"(the mesh processes all records, like the reference's "
              f"MPI rank mapping); ignoring -l", file=sys.stderr)
    if use_ext:
        edges = None  # the stream IS the load; downstream guards on None
    else:
        edges = load_edges(graph_filename, part, num_parts) \
            if not use_mesh else load_edges(graph_filename)
        if verbose:
            nodes, nedges = graph_stats(edges)
            print(f"Nodes:{nodes} Edges:{nedges}")
    if is_leader:
        print_phase("Loaded graph", clock.phase_seconds())

    widths = None

    map_only = False
    if use_mesh and jxn_mode:
        # The kids/pst/jxn tables are a host-side feature (dynamic shapes;
        # SURVEY §7); with -i/-r they run as the single-worker equivalent:
        # device degree sort, then the host parameterized insert — matching
        # a 1-rank MPI run of the reference with the same jopts.
        from ..core.jxn import build_forest_jxn
        from ..ops.sort import degree_sequence_device
        if not use_mesh_sort and sequence_filename:
            seq = read_sequence(sequence_filename)
        else:
            seq = degree_sequence_device(edges.tail, edges.head)
        _finish_sort(seq, use_mesh_sort, sequence_filename, clock,
                     leader=is_leader, writer=proc0)
        jopts = _make_jopts(make_kids, make_pst, make_jxn, memory_limit,
                            width_limit, find_max_width)
        forest, seq, widths = build_forest_jxn(
            edges.tail, edges.head, seq, jopts)
        if is_leader:
            print_phase("Mapped", clock.phase_seconds())
            if use_mesh_reduce:
                print_phase("Reduced", clock.phase_seconds())
    elif use_mesh:
        # Fused SPMD program over the device mesh: sort + map [+ reduce].
        import jax

        from ..parallel.build import build_graph_distributed
        # SHEEP_WORKERS (set by the scripts to $WORKERS) fixes the logical
        # worker count; the mesh itself is capped by the device count — the
        # merged result is identical for any mesh size.
        workers = int(os.environ.get("SHEEP_WORKERS") or 0) \
            or len(jax.devices())
        mesh_workers = min(workers, len(jax.devices()))
        if jax.process_count() > 1:
            # Multi-host: every process participates in the SPMD program,
            # so the mesh must span all global devices — a smaller mesh
            # would exclude later hosts' devices while those processes
            # still drive the program (no addressable shards -> crash).
            mesh_workers = len(jax.devices())
        given_seq = None
        if not use_mesh_sort and sequence_filename:
            given_seq = read_sequence(sequence_filename)
        # -i without -r: save exactly `workers` partial trees for the
        # file-path reduce tournament (reference rank-suffixed %02dr0.tre
        # naming, graph2tree.cpp:146-149).  When the worker count fits the
        # mesh, partials are built on device in one SPMD dispatch (each
        # mesh shard is a partial graph); with more workers than devices
        # the host builds partial_range slices instead (the reference's
        # OOM regime, where ranks outnumber cores too).
        map_only = (use_mesh_sort and not use_mesh_reduce
                    and output_filename != "" and partitions == 0)
        if map_only:
            from ..io.edges import EdgeList, partial_range
            from ..ops.sort import degree_sequence_device
            seq = given_seq if given_seq is not None else \
                degree_sequence_device(edges.tail, edges.head)
            _finish_sort(seq, use_mesh_sort, sequence_filename, clock,
                         leader=is_leader, writer=proc0)
            max_vid = edges.max_vid
            # Multi-host: the device path's mesh must span all global
            # devices (a smaller mesh would exclude later hosts' devices
            # while their processes still drive the program); any other
            # worker count takes the host fallback, which has no
            # collectives and keeps the W-partials file contract.
            mesh_ok = jax.process_count() == 1 \
                or workers == len(jax.devices())
            if workers <= len(jax.devices()) and len(edges.tail) and mesh_ok:
                from ..parallel.build import map_graph_distributed
                _, partials = map_graph_distributed(
                    edges.tail, edges.head, num_workers=workers, seq=seq)
                if proc0:
                    sig = _tree_sig(seq)
                    for w, f in enumerate(partials):
                        write_tree(f"{output_filename}{w:02d}r0.tre",
                                   f.parent, f.pst_weight, sig=sig)
                # -f/-c/-t report worker 0's partial view, like the
                # reference's rank 0 with its partial graph load.
                forest = partials[0]
                shard = -(-len(edges.tail) // workers)
                a0, b0 = 0, min(shard, len(edges.tail))
            else:
                forest = None
                sig = _tree_sig(seq)
                for w in range(workers):
                    a, b = partial_range(edges.num_edges, w + 1, workers)
                    f = build_forest(edges.tail[a:b], edges.head[a:b], seq,
                                     max_vid=max_vid)
                    if proc0:
                        write_tree(f"{output_filename}{w:02d}r0.tre",
                                   f.parent, f.pst_weight, sig=sig)
                    if forest is None:
                        forest = f
                        a0, b0 = a, b
                    if not proc0:
                        # non-leader process: this host loop has no
                        # collectives, and all writes are dropped — only
                        # worker 0's view (for -f/-t/-c) is needed
                        break
            edges = EdgeList(edges.tail[a0:b0], edges.head[a0:b0],
                             file_edges=edges.file_edges, start=a0)
        elif rt_cfg is not None:
            # Fault-tolerant build (--checkpoint-dir / SHEEP_CHECKPOINT_DIR):
            # checkpointed chunk loops, retry-with-backoff, and the
            # mesh -> single-chip -> host degradation ladder.  Bit-identical
            # results; the pipelined fast paths are traded for survivability.
            from ..runtime.driver import build_graph_resilient
            seq, forest = build_graph_resilient(
                edges.tail, edges.head, num_workers=mesh_workers,
                seq=given_seq, max_vid=edges.max_vid, config=rt_cfg)
            _finish_sort(seq, use_mesh_sort, sequence_filename, clock,
                         leader=is_leader, writer=proc0)
        else:
            seq, forest = build_graph_distributed(
                edges.tail, edges.head, num_workers=mesh_workers,
                seq=given_seq)
            _finish_sort(seq, use_mesh_sort, sequence_filename, clock,
                         leader=is_leader, writer=proc0)
        if is_leader:
            print_phase("Mapped", clock.phase_seconds())
            if use_mesh_reduce:
                print_phase("Reduced", clock.phase_seconds())
    elif use_ext:
        # Out-of-core serial path (ISSUE 9): two streamed passes, no jax,
        # no in-RAM edge list.  Same phase grammar as the serial path.
        from ..ops.extmem import build_forest_extmem, \
            streaming_degree_sequence
        ext_kw: dict = {}
        if rt_cfg is not None:
            ext_kw = dict(checkpoint_dir=rt_cfg.checkpoint_dir,
                          resume=rt_cfg.resume,
                          max_retries=rt_cfg.max_retries,
                          backoff_base_s=rt_cfg.backoff_base_s,
                          checkpoint_every=rt_cfg.checkpoint_every,
                          integrity=rt_cfg.integrity,
                          governor=rt_cfg.governor)
        if sequence_filename:
            seq = read_sequence(sequence_filename)
        else:
            seq, _, _ = streaming_degree_sequence(graph_filename)
        if is_leader:
            print_phase("Sorted", clock.phase_seconds())
        seq, forest = build_forest_extmem(graph_filename, seq=seq, **ext_kw)
        if is_leader:
            print_phase("Mapped", clock.phase_seconds())
    else:
        if sequence_filename:
            seq = read_sequence(sequence_filename)
        else:
            seq = degree_sequence(edges.tail, edges.head)
        if is_leader:
            print_phase("Sorted", clock.phase_seconds())
        if jxn_mode:
            from ..core.jxn import build_forest_jxn
            jopts = _make_jopts(make_kids, make_pst, make_jxn, memory_limit,
                                width_limit, find_max_width)
            forest, seq, widths = build_forest_jxn(
                edges.tail, edges.head, seq, jopts)
        elif rt_cfg is not None:
            # Serial path with fault tolerance: the single-chip chunked
            # driver under checkpoint/retry, degrading to the host oracle
            # (no mesh rung — the user did not ask for -i/-r).
            import dataclasses

            from .common import init_compile_cache
            init_compile_cache()
            from ..runtime.driver import build_graph_resilient
            serial_cfg = dataclasses.replace(
                rt_cfg, ladder=("single", "host"))
            _, forest = build_graph_resilient(
                edges.tail, edges.head, seq=seq, max_vid=edges.max_vid,
                config=serial_cfg)
        else:
            forest = build_forest(edges.tail, edges.head, seq,
                                  max_vid=edges.max_vid)
        if is_leader:
            print_phase("Mapped", clock.phase_seconds())

    # under --ext the records never loaded; every vid with a record has
    # nonzero degree and is therefore in the sequence, so seq.max() IS
    # the file's max vid
    max_vid = edges.max_vid if edges is not None else \
        (int(np.asarray(seq).max()) if len(seq) else 0)
    if partitions != 0:
        p = Partition.from_forest(seq, forest, partitions,
                                  max_vid=max_vid)
        if output_filename:
            if proc0:
                prefix = output_filename + \
                    ("-w0000-p" if use_mesh_reduce else "")
                p.write_partitioned_graph(edges.tail, edges.head, seq,
                                          prefix, max_vid=max_vid)
        elif is_leader:
            p.print()
    elif output_filename and not map_only and proc0:
        # Serial fast path builds straight into the output file
        # (graph2tree.cpp:185-188); with -r only the leader saves (:217-218).
        write_tree(output_filename, forest.parent, forest.pst_weight,
                   sig=_tree_sig(seq))

    # Diagnostics print from process 0 only in multi-host runs (rank-0
    # grammar; every process holds the same replicated result anyway).
    # Single-process behavior is unchanged — proc0 is True there even for
    # non-leader map workers.
    if verbose and proc0:
        print_phase("Built", clock.total_seconds())

    if do_faqs and proc0:
        compute_facts(forest, widths=widths).print()
    if do_print and proc0:
        print_tree(seq, forest.parent, forest.pst_weight)
    if do_validate and proc0:
        if edges is None:
            print("warning: -c needs the in-RAM edge list; skipped under "
                  "the external-memory build", file=sys.stderr)
        elif is_valid_forest(forest, edges.tail, edges.head, seq,
                             max_vid=edges.max_vid):
            print("Tree is valid.")
        else:
            print("ERROR: Tree is not valid.")

    if verbose and proc0:
        print_phase("Finished", clock.total_seconds())
    return 0


if __name__ == "__main__":
    sys.exit(main())
