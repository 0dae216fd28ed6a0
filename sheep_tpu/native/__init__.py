"""ctypes bindings to the C++ host runtime (``libsheep_native-<hash>.so``).

The library is built on demand with ``make`` (g++ is part of the toolchain;
pybind11 is not, so the ABI is plain C over caller-allocated numpy buffers).
Its file name carries a hash of the source and the Makefile, so a stale or
foreign build is never loaded.  If the toolchain is unavailable the callers
fall back to the numpy oracle — ``available()`` reports which path is live.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

import numpy as np

from ..obs import trace as _obs

_DIR = os.path.dirname(os.path.abspath(__file__))
_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_tried = False

_u32p = np.ctypeslib.ndpointer(np.uint32, flags="C_CONTIGUOUS")
_i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
_i32p = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
_u64p = np.ctypeslib.ndpointer(np.uint64, flags="C_CONTIGUOUS")
_u8p = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")


def so_path(native_dir: str = _DIR) -> str:
    """The library built from ``native_dir``'s current source: its name
    is keyed on a hash of ``src/sheep_native.cpp`` and the ``Makefile``."""
    h = hashlib.sha256()
    for rel in ("Makefile", os.path.join("src", "sheep_native.cpp")):
        with open(os.path.join(native_dir, rel), "rb") as f:
            h.update(f.read())
    return os.path.join(native_dir,
                        f"libsheep_native-{h.hexdigest()[:16]}.so")


def ensure_built(native_dir: str = _DIR) -> str:
    """Build the library for ``native_dir``'s source unless that exact
    build exists; returns its path.  Raises OSError or
    CalledProcessError when the toolchain fails."""
    so = so_path(native_dir)
    if not os.path.exists(so):
        # Concurrent CLI processes may race to build: compile to a
        # process-unique name and publish with an atomic rename.
        tmp = f"{so}.{os.getpid()}.tmp"
        try:
            subprocess.run(
                ["make", "-C", native_dir, f"OUT={os.path.basename(tmp)}"],
                check=True,
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
            os.replace(tmp, so)
        finally:
            try:
                os.unlink(tmp)
            except OSError:
                pass
    return so


def _load() -> ctypes.CDLL | None:
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        try:
            lib = ctypes.CDLL(ensure_built())
            _bind(lib)
        except (OSError, subprocess.CalledProcessError, AttributeError):
            # no toolchain, wrong arch, or a library lacking a symbol —
            # fall back to the numpy oracle
            return None
        _lib = lib
        return _lib


def _bind(lib: ctypes.CDLL) -> None:
    """Declare the C ABI; raises AttributeError on a stale library."""
    lib.sheep_build_forest.restype = ctypes.c_int
    lib.sheep_build_forest.argtypes = [
        _u32p, _u32p, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_void_p, _u32p, _u32p, ctypes.c_void_p]
    lib.sheep_edges_to_links.restype = ctypes.c_int64
    lib.sheep_edges_to_links.argtypes = [
        _u32p, _u32p, ctypes.c_int64, _u32p, ctypes.c_int64, _u32p, _u32p]
    lib.sheep_build_forest_edges.restype = ctypes.c_int
    lib.sheep_build_forest_edges.argtypes = [
        _u32p, _u32p, ctypes.c_int64, _u32p, ctypes.c_int64,
        ctypes.c_int64, _u32p, _u32p, ctypes.c_void_p]
    lib.sheep_build_forest_links_begin.restype = ctypes.c_int
    lib.sheep_build_forest_links_begin.argtypes = [
        ctypes.c_int64, ctypes.c_void_p, _u32p, _u32p, _u32p]
    lib.sheep_build_forest_links_block.restype = ctypes.c_int64
    lib.sheep_build_forest_links_block.argtypes = [
        _u32p, _u32p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int32, _u32p, _u32p, _u32p]
    lib.sheep_build_forest_links_finish.restype = ctypes.c_int
    lib.sheep_build_forest_links_finish.argtypes = [
        ctypes.c_int64, _u32p, _u32p]
    lib.sheep_forward_partition.restype = ctypes.c_int64
    lib.sheep_forward_partition.argtypes = [
        _u32p, _i64p, ctypes.c_int64, ctypes.c_int64, _i32p]
    lib.sheep_degree_histogram.restype = ctypes.c_int
    lib.sheep_degree_histogram.argtypes = [
        _u32p, _u32p, ctypes.c_int64, ctypes.c_int64, _i64p]
    lib.sheep_degree_histogram_acc.restype = ctypes.c_int
    lib.sheep_degree_histogram_acc.argtypes = [
        _u32p, _u32p, ctypes.c_int64, ctypes.c_int64, _i64p]
    lib.sheep_degree_sequence.restype = ctypes.c_int64
    lib.sheep_degree_sequence.argtypes = [
        _i64p, ctypes.c_int64, _u32p]
    lib.sheep_degree_sequence_edges.restype = ctypes.c_int64
    lib.sheep_degree_sequence_edges.argtypes = [
        _u32p, _u32p, ctypes.c_int64, ctypes.c_int64, _u32p]
    lib.sheep_jxn_build.restype = ctypes.c_int64
    lib.sheep_jxn_build.argtypes = [
        _u32p, _u32p, ctypes.c_int64, _u32p, ctypes.c_int64,
        ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        _u32p, _u32p, _u32p, _i64p]
    lib.sheep_fennel_vertex.restype = ctypes.c_int
    lib.sheep_fennel_vertex.argtypes = [
        _u32p, _u32p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_double, ctypes.c_int, _i64p]
    lib.sheep_fennel_edges.restype = ctypes.c_int
    lib.sheep_fennel_edges.argtypes = [
        _u32p, _u32p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_double, _i64p]
    lib.sheep_eval_block.restype = ctypes.c_int64
    lib.sheep_eval_block.argtypes = [
        _u32p, _u32p, ctypes.c_int64, _i64p, ctypes.c_int64,
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int32, _u64p, _u64p, ctypes.c_void_p, ctypes.c_void_p,
        _u8p, _i64p, _i64p, _i64p, ctypes.c_int64]
    lib.sheep_native_omp.restype = ctypes.c_int
    lib.sheep_native_omp.argtypes = []
    lib.sheep_native_threads.restype = ctypes.c_int
    lib.sheep_native_threads.argtypes = []
    lib.sheep_threads_for.restype = ctypes.c_int
    lib.sheep_threads_for.argtypes = [ctypes.c_int64]
    lib.sheep_omp_max_threads.restype = ctypes.c_int
    lib.sheep_omp_max_threads.argtypes = []
    lib.sheep_last_thread_stats.restype = ctypes.c_int
    lib.sheep_last_thread_stats.argtypes = [
        ctypes.POINTER(ctypes.c_double), ctypes.c_int]


def available() -> bool:
    return _load() is not None


# -- threading (round-14) ---------------------------------------------------
# SHEEP_NATIVE_THREADS (resolved by the governor from SHEEP_LEG_CORES /
# affinity / cgroup quota, resources/governor.py) arms the kernels'
# OpenMP decomposition: per-thread partial forests/histograms over
# contiguous slices, merged deterministically (bit-identical to T=1 for
# every thread count).  The LIBRARY is the authority on what actually
# runs — a build compiled without OpenMP reports threads=1 no matter
# what the environment says.


def omp_compiled() -> bool:
    """True when the loaded library was compiled with OpenMP (the
    Makefile probes the toolchain and degrades to a serial build)."""
    lib = _load()
    return bool(lib is not None and lib.sheep_native_omp())


def resolve_threads() -> int:
    """The resolved ``SHEEP_NATIVE_THREADS`` of the loaded library —
    what an ungated kernel call would use (1 without OpenMP)."""
    lib = _load()
    return int(lib.sheep_native_threads()) if lib is not None else 1


def threads_for(m: int) -> int:
    """Threads a kernel call over ``m`` records/links will ACTUALLY use
    (after the engagement floor and per-slice-work gates)."""
    lib = _load()
    return int(lib.sheep_threads_for(m)) if lib is not None else 1


def omp_max_threads() -> int:
    """``omp_get_max_threads()`` of the loaded runtime (1 without
    OpenMP) — the env_capture field bench records embed."""
    lib = _load()
    return int(lib.sheep_omp_max_threads()) if lib is not None else 1


def _threads_live() -> bool:
    """Cheap pre-gate: skip the per-call stats read entirely when no
    thread count is configured (the overwhelming default path)."""
    return os.environ.get("SHEEP_NATIVE_THREADS", "") not in ("", "0", "1")


def _annotate_threads(sp) -> None:
    """Merge the last kernel call's thread telemetry into its span:
    ``threads`` (what the kernel really used — the gates may have picked
    1) and per-thread busy seconds, the flight recorder's arbiter for
    whether a forced T did parallel work or just time-shared a core."""
    lib = _lib
    if lib is None:
        return
    buf = (ctypes.c_double * 64)()
    used = int(lib.sheep_last_thread_stats(buf, 64))
    if used > 1:
        sp.annotate(threads=used,
                    thread_busy_s=[round(buf[i], 6) for i in range(used)])
    else:
        sp.annotate(threads=1)


def build_forest_links(lo: np.ndarray, hi: np.ndarray, n: int,
                       pst: np.ndarray | None = None,
                       compute_pre: bool = False):
    """Native elimination-forest build; returns (parent, pst) uint32 [n],
    plus a pre_weight array (lib/jnode.h:174-176) when ``compute_pre``."""
    lib = _load()
    assert lib is not None
    lo = np.ascontiguousarray(lo, dtype=np.uint32)
    hi = np.ascontiguousarray(hi, dtype=np.uint32)
    parent = np.empty(n, dtype=np.uint32)
    pst_out = np.empty(n, dtype=np.uint32)
    pst_ptr = None
    if pst is not None:
        pst = np.ascontiguousarray(pst, dtype=np.uint32)
        pst_ptr = pst.ctypes.data_as(ctypes.c_void_p)
    pre_out = np.empty(n, dtype=np.uint32) if compute_pre else None
    pre_ptr = pre_out.ctypes.data_as(ctypes.c_void_p) if compute_pre else None
    with _obs.span("native.build_forest", links=len(lo), n=n) as sp:
        rc = lib.sheep_build_forest(lo, hi, len(lo), n, pst_ptr, parent,
                                    pst_out, pre_ptr)
        if _threads_live():
            _annotate_threads(sp)
    if rc != 0:
        raise RuntimeError(f"sheep_build_forest failed rc={rc}")
    if compute_pre:
        return parent, pst_out, pre_out
    return parent, pst_out


class LinksFold:
    """Resumable native link fold (sheep_build_forest_links_begin/_block/
    _finish): the exact forest build consumed one ascending-hi window at a
    time, so the streaming handoff can fold window k while window k+1 is
    still in flight and the full link table never materializes host-side.

    Blocks must arrive in ascending-hi order (an equal-hi group may split
    across adjacent blocks — exact, see the kernel comment); ``block``
    raises ValueError on an out-of-order window so a mis-sliced stream
    fails loudly instead of building a different forest.  ``pst`` None
    means the fold accumulates pst from the streamed records themselves —
    exact only when the windows together carry the ORIGINAL link multiset
    (the immediate-handoff stream); reduced/rewritten links need the
    prep-time pst passed here.
    """

    def __init__(self, n: int, pst: np.ndarray | None = None):
        lib = _load()
        assert lib is not None
        self._lib = lib
        self.n = n
        self.accumulate_pst = pst is None
        self.parent = np.empty(n, dtype=np.uint32)
        self.pst = np.empty(n, dtype=np.uint32)
        self._uf = np.empty(n, dtype=np.uint32)
        self._bound = 0
        self._done = False
        pst_ptr = None
        if pst is not None:
            pst = np.ascontiguousarray(pst, dtype=np.uint32)
            pst_ptr = pst.ctypes.data_as(ctypes.c_void_p)
        rc = lib.sheep_build_forest_links_begin(n, pst_ptr, self.parent,
                                                self.pst, self._uf)
        if rc != 0:
            raise RuntimeError(f"sheep_build_forest_links_begin rc={rc}")

    def block(self, lo: np.ndarray, hi: np.ndarray) -> None:
        """Fold one window of links (uint32-safe arrays, every lo < n,
        linked hi >= every previous window's linked hi)."""
        assert not self._done, "fold already finished"
        lo = np.ascontiguousarray(lo, dtype=np.uint32)
        hi = np.ascontiguousarray(hi, dtype=np.uint32)
        with _obs.span("native.links_fold.block", links=len(lo)) as sp:
            r = self._lib.sheep_build_forest_links_block(
                lo, hi, len(lo), self.n, self._bound,
                1 if self.accumulate_pst else 0, self.parent, self.pst,
                self._uf)
            if _threads_live():
                _annotate_threads(sp)
        if r == -7:
            raise ValueError(
                "out-of-order fold window: a linked hi precedes the "
                "previous window's range — windows must ascend by hi")
        if r == -3:
            raise ValueError(f"malformed link: lo >= n ({self.n})")
        if r < 0:
            raise RuntimeError(f"sheep_build_forest_links_block rc={r}")
        self._bound = int(r)

    def finish(self) -> tuple[np.ndarray, np.ndarray]:
        """Seal the fold; returns (parent, pst) uint32 [n]."""
        with _obs.span("native.links_fold.finish", n=self.n):
            rc = self._lib.sheep_build_forest_links_finish(
                self.n, self.parent, self._uf)
        if rc != 0:
            raise RuntimeError(f"sheep_build_forest_links_finish rc={rc}")
        self._done = True
        return self.parent, self.pst


def blocked_enabled() -> bool:
    """The cache-blocked kernel gate (SHEEP_NATIVE_BLOCKED, default on).
    Read per call so A/B arms can flip it without reloading the library;
    the C++ side reads the same variable for its internal dispatch."""
    return os.environ.get("SHEEP_NATIVE_BLOCKED", "1") != "0"


def build_forest_edges(tail: np.ndarray, head: np.ndarray, pos: np.ndarray,
                       n: int, compute_pre: bool = False):
    """Fused edge->forest build (round-6): maps records through the
    position table and groups into the cache-blocked union-find without
    materializing the intermediate link arrays.  Returns (parent, pst)
    uint32 [n] (+ pre when ``compute_pre``), bit-identical to
    edges_to_links + build_forest_links."""
    lib = _load()
    assert lib is not None
    tail = np.ascontiguousarray(tail, dtype=np.uint32)
    head = np.ascontiguousarray(head, dtype=np.uint32)
    pos = np.ascontiguousarray(pos, dtype=np.uint32)
    parent = np.empty(n, dtype=np.uint32)
    pst_out = np.empty(n, dtype=np.uint32)
    pre_out = np.empty(n, dtype=np.uint32) if compute_pre else None
    pre_ptr = pre_out.ctypes.data_as(ctypes.c_void_p) if compute_pre else None
    with _obs.span("native.build_forest_edges", records=len(tail),
                   n=n) as sp:
        rc = lib.sheep_build_forest_edges(tail, head, len(tail), pos,
                                          len(pos), n, parent, pst_out,
                                          pre_ptr)
        if _threads_live():
            _annotate_threads(sp)
    if rc != 0:
        raise RuntimeError(f"sheep_build_forest_edges failed rc={rc}")
    if compute_pre:
        return parent, pst_out, pre_out
    return parent, pst_out


def edges_to_links(tail: np.ndarray, head: np.ndarray, pos: np.ndarray):
    """Map edge records through a position table; drops self-loops and
    absent vids.  Returns (lo, hi) uint32 arrays."""
    lib = _load()
    assert lib is not None
    tail = np.ascontiguousarray(tail, dtype=np.uint32)
    head = np.ascontiguousarray(head, dtype=np.uint32)
    pos = np.ascontiguousarray(pos, dtype=np.uint32)
    lo = np.empty(len(tail), dtype=np.uint32)
    hi = np.empty(len(tail), dtype=np.uint32)
    k = lib.sheep_edges_to_links(tail, head, len(tail), pos, len(pos), lo, hi)
    return lo[:k], hi[:k]


def forward_partition(parent: np.ndarray, weights: np.ndarray,
                      max_component: int) -> np.ndarray:
    """Native FFD tree partition; returns int32 part array."""
    lib = _load()
    assert lib is not None
    parent = np.ascontiguousarray(parent, dtype=np.uint32)
    weights = np.ascontiguousarray(weights, dtype=np.int64)
    parts = np.empty(len(parent), dtype=np.int32)
    with _obs.span("native.forward_partition", n=len(parent)):
        rc = lib.sheep_forward_partition(parent, weights, len(parent),
                                         max_component, parts)
    if rc == -2:
        raise ValueError(
            f"max_component {max_component} smaller than the heaviest node; "
            f"request fewer partitions or a larger balance factor")
    if rc == -3:
        raise ValueError(
            "corrupt tree: a parent entry is neither INVALID nor a valid "
            "node id (malformed .tre input?)")
    if rc < 0:
        raise RuntimeError(f"sheep_forward_partition failed rc={rc}")
    return parts


def degree_histogram(tail: np.ndarray, head: np.ndarray, n: int) -> np.ndarray:
    lib = _load()
    assert lib is not None
    tail = np.ascontiguousarray(tail, dtype=np.uint32)
    head = np.ascontiguousarray(head, dtype=np.uint32)
    deg = np.empty(n, dtype=np.int64)
    rc = lib.sheep_degree_histogram(tail, head, len(tail), n, deg)
    if rc == -3:
        raise ValueError(
            f"corrupt edge records: a vid is out of range for n={n}")
    if rc != 0:
        raise RuntimeError(f"sheep_degree_histogram failed rc={rc}")
    return deg


def degree_histogram_acc(tail: np.ndarray, head: np.ndarray,
                         deg: np.ndarray) -> None:
    """Add one edge block's degree contributions INTO ``deg`` (int64
    [n], caller-owned, NOT zeroed here) — the streaming accumulator of
    the out-of-core degree pass (ops/extmem.py): per-block adds fold into
    one histogram with no per-block allocation, exactly equal to the
    one-shot histogram over the concatenated records."""
    lib = _load()
    assert lib is not None
    tail = np.ascontiguousarray(tail, dtype=np.uint32)
    head = np.ascontiguousarray(head, dtype=np.uint32)
    assert deg.dtype == np.int64 and deg.flags["C_CONTIGUOUS"]
    with _obs.span("native.degree_histogram_acc", records=len(tail)) as sp:
        rc = lib.sheep_degree_histogram_acc(tail, head, len(tail),
                                            len(deg), deg)
        if _threads_live():
            _annotate_threads(sp)
    if rc == -3:
        raise ValueError(
            f"corrupt edge records: a vid is out of range for n={len(deg)}")
    if rc != 0:
        raise RuntimeError(f"sheep_degree_histogram_acc failed rc={rc}")


def jxn_build(tail: np.ndarray, head: np.ndarray, seq: np.ndarray,
              n_vid: int, width_limit: int, memory_limit: int,
              make_pad: bool, make_pst: bool, make_jxn: bool,
              find_max_width: bool, do_rooting: bool):
    """Native parameterized jxn insert (sheep_jxn_build).

    Returns (parent, pst, out_seq, widths) trimmed to the effective node
    count.  Raises MemoryError past memory_limit (rc -4) like the oracle.
    """
    lib = _load()
    assert lib is not None
    tail = np.ascontiguousarray(tail, dtype=np.uint32)
    head = np.ascontiguousarray(head, dtype=np.uint32)
    seq = np.ascontiguousarray(seq, dtype=np.uint32)
    cap = len(seq)
    parent = np.empty(cap, dtype=np.uint32)
    pst = np.empty(cap, dtype=np.uint32)
    out_seq = np.empty(cap, dtype=np.uint32)
    widths = np.empty(cap, dtype=np.int64)
    flags = (1 * make_pad) | (4 * make_pst) | (8 * make_jxn) | \
        (16 * find_max_width) | (32 * do_rooting)
    n_out = lib.sheep_jxn_build(tail, head, len(tail), seq, cap, n_vid,
                                width_limit, memory_limit, flags,
                                parent, pst, out_seq, widths)
    if n_out == -4:
        raise MemoryError(
            f"pst/jxn tables exceed memory_limit={memory_limit}")
    if n_out < 0:
        raise ValueError(f"sheep_jxn_build failed rc={n_out}")
    k = int(n_out)
    return (parent[:k].copy(), pst[:k].copy(), out_seq[:k].copy(),
            widths[:k].copy())


def fennel_vertex(tail: np.ndarray, head: np.ndarray, n_vid: int,
                  num_parts: int, balance_factor: float,
                  edge_balanced: bool) -> np.ndarray:
    """Native greedy Fennel vertex partition; int64 [n_vid], -1 invalid."""
    lib = _load()
    assert lib is not None
    tail = np.ascontiguousarray(tail, dtype=np.uint32)
    head = np.ascontiguousarray(head, dtype=np.uint32)
    parts = np.empty(n_vid, dtype=np.int64)
    rc = lib.sheep_fennel_vertex(tail, head, len(tail), n_vid, num_parts,
                                 balance_factor, int(edge_balanced), parts)
    if rc != 0:
        raise ValueError(f"sheep_fennel_vertex failed rc={rc}")
    return parts


def fennel_edges(tail: np.ndarray, head: np.ndarray, n_vid: int,
                 num_parts: int, balance_factor: float) -> np.ndarray:
    """Native streaming Fennel edge partition; int64 [num_records]."""
    lib = _load()
    assert lib is not None
    tail = np.ascontiguousarray(tail, dtype=np.uint32)
    head = np.ascontiguousarray(head, dtype=np.uint32)
    eparts = np.empty(len(tail), dtype=np.int64)
    rc = lib.sheep_fennel_edges(tail, head, len(tail), n_vid, num_parts,
                                balance_factor, eparts)
    if rc != 0:
        raise ValueError(f"sheep_fennel_edges failed rc={rc}")
    return eparts


def degree_sequence_from_edges(tail: np.ndarray, head: np.ndarray,
                               n: int) -> np.ndarray | None:
    """Fused histogram + counting-sort degree sequence (round-6): one
    call, uint32 histogram internally.  Returns None when the record or
    degree range outgrows the fused kernel's buckets (callers fall back
    to the two-call path), raises on out-of-range vids."""
    lib = _load()
    assert lib is not None
    tail = np.ascontiguousarray(tail, dtype=np.uint32)
    head = np.ascontiguousarray(head, dtype=np.uint32)
    seq = np.empty(n, dtype=np.uint32)
    with _obs.span("native.degree_sequence_edges", records=len(tail)) as sp:
        k = lib.sheep_degree_sequence_edges(tail, head, len(tail), n, seq)
        if _threads_live():
            _annotate_threads(sp)
    if k == -3:
        raise ValueError(
            f"corrupt edge records: a vid is out of range for n={n}")
    if k < 0:
        return None
    return seq[:k].copy()


def degree_sequence_from_degrees(deg: np.ndarray) -> np.ndarray | None:
    """Counting-sort degree sequence (ascending degree, vid tie break).

    Returns None when the degree range is too wide for counting buckets
    (a multigraph hub can push max_degree far past n); callers fall back
    to the comparison sort.
    """
    deg = np.ascontiguousarray(deg, dtype=np.int64)
    if len(deg) and int(deg.max()) > max(4 * len(deg), 1 << 20):
        return None
    lib = _load()
    assert lib is not None
    seq = np.empty(len(deg), dtype=np.uint32)
    with _obs.span("native.degree_sequence", n=len(deg)) as sp:
        k = lib.sheep_degree_sequence(deg, len(deg), seq)
        if _threads_live():
            _annotate_threads(sp)
    return seq[:k].copy()


def eval_block(tail: np.ndarray, head: np.ndarray, parts: np.ndarray,
               pos: np.ndarray | None, w0: int, words: int,
               first_window: bool,
               m_vcom: np.ndarray, m_hash: np.ndarray,
               m_down: np.ndarray | None, m_up: np.ndarray | None,
               deg_mask: np.ndarray, hash_loads: np.ndarray,
               down_loads: np.ndarray, up_loads: np.ndarray,
               num_parts: int) -> int:
    """One block of the part-set bitmap evaluator over the parts
    [w0, w0 + 64*words) (updates the bitmaps of ``len(parts) * words``
    uint64 and the load counters in place); returns the edges_cut
    increment.  All array arguments must be the caller-owned state
    buffers — they are mutated, not copied.
    """
    lib = _load()
    assert lib is not None
    tail = np.ascontiguousarray(tail, dtype=np.uint32)
    head = np.ascontiguousarray(head, dtype=np.uint32)
    # the kernel indexes X*words + word, and writes m_down/m_up whenever
    # pos is given: a short or missing bitmap would be a heap overrun or a
    # null-pointer store
    size = len(parts) * words
    maps = (m_vcom, m_hash) if pos is None else (m_vcom, m_hash, m_down, m_up)
    if words < 1 or len(deg_mask) < len(parts) or \
            any(m is None or m.size != size for m in maps):
        raise ValueError(f"eval_block: bitmaps must hold len(parts) x "
                         f"words = {size} uint64, deg_mask len(parts)")
    pos_ptr, pos_len = 0, 0
    if pos is not None:
        assert pos.dtype == np.uint32 and pos.flags["C_CONTIGUOUS"]
        pos_ptr, pos_len = pos.ctypes.data, len(pos)
        assert m_down.dtype == np.uint64 and m_down.flags["C_CONTIGUOUS"]
        assert m_up.dtype == np.uint64 and m_up.flags["C_CONTIGUOUS"]
    # parts / masks / counters go through ndpointer argtypes, which
    # already enforce dtype + contiguity with clear TypeErrors; only the
    # raw-pointer (c_void_p) arguments need manual validation above.
    down_ptr = m_down.ctypes.data if pos is not None else 0
    up_ptr = m_up.ctypes.data if pos is not None else 0
    rc = lib.sheep_eval_block(
        tail, head, len(tail), parts, len(parts), pos_ptr, pos_len,
        w0, words, 1 if first_window else 0, m_vcom, m_hash, down_ptr,
        up_ptr, deg_mask, hash_loads, down_loads, up_loads, num_parts)
    if rc < 0:
        raise ValueError(
            "sheep_eval_block: a vid is out of range of parts/pos, or a "
            "streamed vertex has an invalid part id (e.g. INVALID_PART "
            "-1) — parts must cover every vid in the edge stream")
    return int(rc)
