"""Shared CLI plumbing: phase timers (exact stdout grammar) + graph stats.

The reference prints phase lines like ``Loaded graph in: 1.234000 seconds``
(graph2tree.cpp:167,183,193,200,225,240, %f formatting) and the shell /
plotting layer parses them (data/make-parallel.sh), so the grammar is API.
Millisecond truncation matches std::chrono::duration_cast<milliseconds>.
"""

from __future__ import annotations

import time

import numpy as np

from ..obs import trace as obs


def maybe_start_heartbeat():
    """Start the worker heartbeat when a supervisor launched this process
    with SHEEP_HEARTBEAT_FILE in the environment (supervisor/heartbeat.py).
    Returns the writer (kept alive for the process lifetime) or None —
    unsupervised invocations are unaffected."""
    from ..supervisor.heartbeat import maybe_start_from_env
    return maybe_start_from_env()


def init_compile_cache() -> str:
    """Give JAX's persistent compilation cache one fixed directory; every
    entry point that touches JAX calls this before its first compile.

    JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself, so when it is set
    nothing else is configured.  Otherwise the cache lives in
    ``.jax_cache/`` at the checkout root (gitignored): a cold chip run
    compiles for minutes, and the directory is part of the cache key, so
    it must not move between runs.  Returns the directory in use.
    """
    import os

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    import sheep_tpu
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(sheep_tpu.__file__))), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def maybe_init_distributed() -> int:
    """Join a multi-host coordination service when the launcher asks for it.

    The reference distributes `graph2tree -i -r` with `mpiexec` across
    nodes (README:88-89, data/slurm-uk2007); the launcher analog here is
    env vars: SHEEP_COORDINATOR=host:port plus SHEEP_NUM_PROCESSES /
    SHEEP_PROCESS_ID per process.  After joining, jax.devices() spans all
    hosts and the same SPMD build runs over the DCN mesh.  Returns this
    process's index (0 when not distributed) for leader gating — the
    reference's rank-0 logic (graph2tree.cpp:158-159).
    """
    import os

    coord = os.environ.get("SHEEP_COORDINATOR")
    if not coord:
        return 0
    from ..parallel import init_distributed
    num = os.environ.get("SHEEP_NUM_PROCESSES")
    pid = os.environ.get("SHEEP_PROCESS_ID")
    init_distributed(coordinator_address=coord,
                     num_processes=int(num) if num else None,
                     process_id=int(pid) if pid else None)
    import jax
    return jax.process_index()


def runtime_config_from_opts(opts):
    """Fold the fault-tolerance long options (--checkpoint-dir DIR,
    --resume, --max-retries N) plus their env fallbacks
    (SHEEP_CHECKPOINT_DIR / SHEEP_RESUME / SHEEP_MAX_RETRIES, the
    dist-partition.sh -C contract) into a runtime.RuntimeConfig.

    Returns None when no checkpoint dir is configured anywhere — the
    caller then keeps the plain fast path.  --resume / --max-retries
    without a checkpoint dir are a configuration error (there is nothing
    to resume from and nothing durable to retry toward): reported, not
    ignored.
    """
    import os

    ckpt_dir = os.environ.get("SHEEP_CHECKPOINT_DIR") or None
    resume = os.environ.get("SHEEP_RESUME", "") == "1"
    max_retries = None
    for o, a in opts:
        if o == "--checkpoint-dir":
            ckpt_dir = a
        elif o == "--resume":
            resume = True
        elif o == "--max-retries":
            max_retries = int(a)
    if ckpt_dir is None:
        if resume or max_retries is not None:
            raise SystemExit(
                "--resume/--max-retries need --checkpoint-dir (or "
                "SHEEP_CHECKPOINT_DIR) to name the checkpoint location")
        return None
    from ..runtime.driver import RuntimeConfig
    overrides = {"checkpoint_dir": ckpt_dir, "resume": resume}
    if max_retries is not None:
        overrides["max_retries"] = max_retries
    return RuntimeConfig.from_env(**overrides)


class PhaseClock:
    """Elapsed-time phases with duration_cast<milliseconds> truncation."""

    def __init__(self):
        self.start = time.perf_counter()
        self.last = 0.0  # total at the previous phase boundary, in ms

    def _total_ms(self) -> int:
        return int((time.perf_counter() - self.start) * 1000)

    def phase_seconds(self) -> float:
        """Seconds since the previous phase boundary."""
        total = self._total_ms()
        out = (total - self.last) / 1000.0
        self.last = total
        return out

    def total_seconds(self) -> float:
        return self._total_ms() / 1000.0


def print_phase(label: str, seconds: float) -> None:
    print(f"{label} in: {seconds:f} seconds", flush=True)


def print_phase_ms(label: str, seconds: float) -> None:
    """merge_trees/degree_sequence style: ``Loaded in: 12ms``."""
    print(f"{label} in: {int(seconds * 1000)}ms", flush=True)


def graph_stats(edges) -> tuple[int, int]:
    """(nodes, edges) as the reference reports them: nodes = vertices with
    nonzero degree (graph_wrapper.h:75-77), edges = file records
    (max_edges/2 of the undirected-doubled graph, :79-81)."""
    with obs.span("cli.stats", records=edges.num_edges):
        deg = edges.degrees()
        return int((deg > 0).sum()), edges.num_edges


def print_tree(seq: np.ndarray, parent: np.ndarray, pst: np.ndarray) -> None:
    """``graph2tree -t`` / JTree::print grammar (lib/jtree.h:60-66,
    lib/jnode.h print: width:w pre:pre pst:pst -> [parent])."""
    for jnid in range(len(seq)):
        width = 1 + int(pst[jnid])
        print("%4d:%-8d%6d:w%6d:pre%6d:pst        ->[%4d]"
              % (jnid, int(seq[jnid]), width, 0, int(pst[jnid]),
                 int(np.uint32(parent[jnid]))))
