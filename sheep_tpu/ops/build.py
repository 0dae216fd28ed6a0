"""Fused single-device build step: edges -> (sequence, elimination forest).

This is the whole ``graph2tree`` compute path as one jitted program with
static shapes — the device analog of load+sort+map (SURVEY §3.1): degree
histogram, (degree, vid) sort, edge->link mapping, forest fixpoint, pst
segment-sum.  The mesh-sharded variant lives in sheep_tpu.parallel.
"""

from __future__ import annotations

import functools
import os
import threading
import time

import numpy as np
import jax
import jax.numpy as jnp

from ..core.forest import Forest
from ..obs import trace as obs
from .forest import forest_fixpoint, pst_weights
from .sort import degree_histogram, degree_order, edge_links


@functools.partial(jax.jit, static_argnames=("n",))
def build_step(tail: jnp.ndarray, head: jnp.ndarray, n: int):
    """Full forward step on edge records (uint32/int32 [E]) over n vid slots.

    Returns (seq, pos, num_active, parent, pst, rounds) — all int32, all
    length n except the scalars.  Positions/parents live in full n-slot
    space; entries for zero-degree vids sit at the tail and are roots with
    pst 0.  ``parent[v] == n`` marks roots.
    """
    deg = degree_histogram(tail, head, n)
    seq, pos, m = degree_order(deg)
    lo, hi = edge_links(tail, head, pos, n)
    parent, rounds = forest_fixpoint(lo, hi, n)
    pst = pst_weights(lo, n)
    return seq, pos, m, parent, pst, rounds


@functools.partial(jax.jit, static_argnames=("n", "with_pst"))
def prepare_links(tail: jnp.ndarray, head: jnp.ndarray, n: int,
                  with_pst: bool = True):
    """Phases before the fixpoint, in one dispatch: degree histogram,
    (degree, vid) sort, edge->link mapping, pst segment-sum.

    Returns (seq, pos, num_active, lo, hi, pst) — pst is computed here
    because the fixpoint rewrites lo in place and pst must count the
    *original* links (jtree.cpp:47-49).  ``with_pst=False`` drops that
    full-E scatter pass (pst is None) for callers that recompute pst on
    the host from their own edge copy (build_graph_hybrid's prefetch) —
    on a backend where every op is priced per element, one pass of E is
    ~1/6 of the whole prep program.
    """
    deg = degree_histogram(tail, head, n)
    seq, pos, m = degree_order(deg)
    lo, hi = edge_links(tail, head, pos, n)
    pst = pst_weights(lo, n) if with_pst else None
    return seq, pos, m, lo, hi, pst


def _finish(seq, m, parent, pst):
    m = int(m)
    seq = _as_u32(np.ascontiguousarray(np.asarray(seq)[:m]))
    # Trimmed to the m active slots; parents of active nodes are active
    # positions (< m), so the converter's n=m sentinel check is exact.
    from .forest import _to_forest
    return seq, _to_forest(np.asarray(parent)[:m], np.asarray(pst)[:m], m)


def build_graph_device(tail: np.ndarray, head: np.ndarray,
                       num_vertices: int | None = None):
    """Host-facing device build: returns (seq uint32 [m], Forest over m).

    Uses the host-orchestrated chunked fixpoint (ops.forest), which is the
    production path on real hardware: bounded per-dispatch execution time
    (no device faults at large n) and live-edge compaction between chunks.
    """
    from .forest import forest_fixpoint_hosted

    n = num_vertices
    if n is None:
        n = int(max(tail.max(initial=0), head.max(initial=0))) + 1 if len(tail) else 0
    if n == 0:
        return np.empty(0, np.uint32), Forest(
            np.empty(0, np.uint32), np.empty(0, np.uint32))
    seq, _, m, lo, hi, pst = prepare_links(
        jnp.asarray(tail), jnp.asarray(head), n)
    parent, _ = forest_fixpoint_hosted(lo, hi, n)
    return _finish(seq, m, parent, pst)


def _host_seq_pst(tail_np: np.ndarray, head_np: np.ndarray, n: int,
                  seq: np.ndarray | None = None):
    """Host-side (seq, pst) identical to the device's prepare_links outputs.

    Same order (degree asc, vid asc — tested equal across all four build
    implementations) and same pst semantics (one count per non-self-loop
    record at the position of its earlier-in-sequence endpoint, absent
    heads included).  A given ``seq`` replaces the degree sort.  Chunked
    gathers keep the peak at ~3 int32 arrays of one block, not of E.
    """
    from ..core.sequence import degree_sequence, sequence_positions

    seq_h = degree_sequence(tail_np, head_np, n) if seq is None \
        else np.asarray(seq, dtype=np.uint32)
    pos = sequence_positions(seq_h, n - 1)
    pst = np.zeros(n, np.int64)
    block = 1 << 24
    for s in range(0, len(tail_np), block):
        # absent vids carry INVALID (0xFFFFFFFF), which as int64 is >= n
        # for every supported n, so min() picks the present endpoint and
        # the lo < n filter drops both-absent pairs
        pt = pos[tail_np[s:s + block]].astype(np.int64)
        ph = pos[head_np[s:s + block]].astype(np.int64)
        lo = np.minimum(pt, ph)
        live = (pt != ph) & (lo < n)
        pst += np.bincount(lo[live], minlength=n)[:n]
    return seq_h, pst.astype(np.uint32)


def build_graph_hybrid(tail: np.ndarray, head: np.ndarray,
                       num_vertices: int | None = None,
                       handoff_factor: int | None = None,
                       host_edges: tuple[np.ndarray, np.ndarray] | None = None,
                       seq: np.ndarray | None = None,
                       perf: dict | None = None):
    """Flagship heterogeneous build: TPU reduction + native union-find tail.

    The device runs the bandwidth-parallel phases (histogram, degree sort,
    link mapping, pst, and a few reduction rounds that kill the ~90% of
    links that are duplicates or star-collapsible); once at most
    ``handoff_factor * n`` live links remain, they transfer to the host and
    the C++ runtime finishes with the exact sequential union-find
    (sheep_native.cpp), which chases pointers at rates no batched device
    round can match.  Sound because every chunk round preserves threshold
    connectivity, and the elimination forest is a function of threshold
    connectivity only (module docstring of ops.forest).

    The handoff is the streaming windowed tail (:func:`_stream_tail`):
    the reduced live set fetches as W ascending hi-quantile windows
    (SHEEP_HANDOFF_WINDOWS; shared quantile rule with the mesh tail
    shard), each folded through the resumable native union-find
    (native.LinksFold) the moment it lands — fold k overlaps fetch k+1
    and the full link table never materializes host-side.  On the cpu
    backend the fetch is a zero-copy view, so the tail instead drops
    the pre-fold device sort and (host_seq_mode) moves the degree
    sequence to the native counting sort, shrinking the device program
    to the link mapping.  A failed fetch or fold raises.

    Returns (seq uint32 [m], Forest over m), bit-identical to the oracle.

    ``handoff_factor`` tunes how reduced the link set must be before the
    transfer (default 8, env SHEEP_HANDOFF_FACTOR): measured on the
    1-core host, stopping after the first dedupe round (factor 8) beats
    reducing all the way to 2n by 3.3x — the native union-find retires
    links far faster than extra device rounds do.

    ``host_edges`` — the same edge records as host numpy arrays, when the
    caller has them (after any real load phase the graph is resident in
    host RAM whether or not it was also uploaded).  With a host copy, seq
    and pst are recomputed on the host concurrently with the device
    reduction instead of fetched from the device — bit-identical either
    way, but 2n*4B less d2h traffic.  Numpy tail/head inputs serve as
    their own host copy automatically.

    ``perf`` — optional dict receiving the reduce + tail breakdown (see
    :func:`reduce_and_finish_native`).  The device rounds, the live
    links at the handoff and the handoff's mode land as one
    ``hybrid.handoff`` trace event; its times are the ``reduce.loop``
    and ``handoff.*`` spans.

    ``seq`` — an externally given elimination order (the `-s`/`-r` case):
    skips the device degree histogram + sort entirely (two fewer full-E
    passes plus the E-sized sort), maps links straight through the
    position table, and honors the absent-vid pst contract (edges to
    vids outside the sequence count toward pst, never the tree —
    jtree.cpp:47-49).
    """
    from .forest import parent_from_links

    if handoff_factor is None:
        handoff_factor = default_handoff_factor()
    n = num_vertices
    if n is None:
        n = int(max(tail.max(initial=0), head.max(initial=0))) + 1 if len(tail) else 0
    if seq is not None and len(seq):
        n = max(n, int(np.asarray(seq).max()) + 1)
    if n == 0:
        return np.empty(0, np.uint32), Forest(
            np.empty(0, np.uint32), np.empty(0, np.uint32))
    if host_edges is None \
            and isinstance(tail, np.ndarray) and isinstance(head, np.ndarray) \
            and (jax.devices()[0].platform != "cpu"
                 or handoff_input_ok()):
        # auto-detect where the host copy buys something real: on
        # accelerators it saves the 2n*4B seq/pst d2h; on the cpu
        # backend under the immediate handoff there is no reduce loop
        # to compete with, and the copy enables the host-seq prep below
        host_edges = (tail, head)
    given_seq = None
    _lazy_pst = None
    acc_ok = False  # may the tail fold count pst from its own stream?
    if seq is None and host_edges is not None and host_seq_mode() \
            and handoff_input_ok():
        # immediate-handoff cpu prep: the native counting-sort degree
        # sequence (~6x the XLA histogram+sort on the same silicon)
        # computed host-side UP FRONT, so the device program shrinks to
        # the link mapping alone.  Bit-identical: the host sequence
        # equals the device's (degree asc, vid asc — tested across all
        # four build implementations) and given_seq_links encodes the
        # same absent-vid contract the device mapping uses.  Every
        # active vid is in this sequence, so no pst-only link is ever
        # masked out — the streamed multiset stays intact and the fold
        # may count pst itself (acc_ok).
        from ..core.sequence import degree_sequence
        with obs.span("prep.seq", n=n):
            seq = degree_sequence(host_edges[0], host_edges[1], n)
        acc_ok = True
    if seq is not None:
        # `-s` fast path: no histogram, no device sort — links map through
        # the given position table (absent-vid contract lives in
        # ops.sort.given_seq_links, shared with the mesh builders)
        from .sort import given_seq_links
        given_seq = np.asarray(seq, dtype=np.uint32)
        with obs.span("prep.map", n=n):
            lo, hi, pst = given_seq_links(tail, head, given_seq, n,
                                          with_pst=host_edges is None)
        m = len(given_seq)
        dev_seq = None
        if pst is None:
            # pst counts the pre-dead-mask lo, so it can't be recovered
            # from the masked arrays — when no host prefetch counts it,
            # rerun the mapping with the scatter included
            def _lazy_pst():
                return given_seq_links(tail, head, given_seq, n)[2]
    else:
        # with a host edge copy the prefetch thread recomputes pst
        # host-side — skip the device's full-E pst scatter; same when
        # the streaming fold will count pst in its own read pass (the
        # immediate-handoff platforms).  Keep the original lo handle so
        # pst can still be materialized on device when nothing else
        # counts it.
        # the span ends when the program's output is ready on the
        # device (observed off this thread, only while tracing), not at
        # the enqueue; m is never donated
        with obs.span("prep", n=n) as sp:
            dev_seq, _, m, lo, hi, pst = prepare_links(
                jnp.asarray(tail), jnp.asarray(head), n,
                with_pst=host_edges is None and not handoff_input_ok())
            sp.end_when_ready(m)
        # full-graph prep: every vid holds a position, so the link
        # multiset carries no maskable pst-only records — the streaming
        # fold may accumulate pst when the loop skips straight to handoff
        acc_ok = True
        if pst is None:
            orig_lo = lo

            def _lazy_pst():
                # module-level pst_weights, eager: one scatter op through
                # jax's global op cache, no throwaway per-closure jit
                return pst_weights(orig_lo, n)
    # every downstream consumer (the prefetch, _finish) reads `seq`:
    # the given host order when supplied, else the device-computed one
    seq = given_seq if given_seq is not None else dev_seq
    # overlap seq/pst with the reduction rounds: with a host edge copy,
    # recompute them on the host (no d2h at all); otherwise fetch them
    # on a second thread while the reduce phase blocks on its own
    # per-chunk syncs, so either way the work hides behind the chunk loop
    fetched: dict = {}
    pre = None
    if acc_ok and given_seq is not None:
        # host-seq streaming prep: seq/m are host-known already and pst
        # comes from the tail fold's own read pass — nothing to prefetch
        # (a tail that cannot count it resolves pst through _lazy_pst)
        fetched = {"seq": given_seq, "m": len(given_seq)}
    else:
        def _prefetch():
            try:
                if host_edges is not None:
                    t_np, h_np = host_edges
                    with obs.span("prep.host", n=n):
                        fetched["seq"], fetched["pst"] = _host_seq_pst(
                            t_np, h_np, n, seq=given_seq)
                    # host seq is already trimmed to the m active slots,
                    # so its length replaces the device scalar fetch
                    fetched["m"] = len(fetched["seq"])
                else:
                    fetched["seq"] = np.asarray(seq)
                    if pst is not None:
                        fetched["pst"] = np.asarray(pst)
            except Exception as exc:  # re-raised by _join_prefetch
                fetched["error"] = exc

        pre = threading.Thread(target=obs.bind(_prefetch), daemon=True)
        pre.start()

    def _join_prefetch():
        if pre is not None:
            pre.join()
            if "error" in fetched:
                raise fetched["error"]

    def _pst_resolved():
        # host-prefetched pst when the thread landed it; else the device
        # pst — materialized lazily when prepare_links skipped the scatter
        if "pst" in fetched:
            return fetched["pst"]
        return pst if pst is not None else _lazy_pst()

    def _pst_after_fetch():
        # resolved only after the link fetch/stream has begun, so the
        # seq/pst prefetch keeps overlapping it
        _join_prefetch()
        return _as_u32(np.asarray(_pst_resolved()))

    # immediate-handoff only where its trade was measured to win — the
    # handoff_input_ok gate shared with ops.stream's final fold
    if perf is None:
        perf = {}
    res = reduce_and_finish_native(
        lo, hi, n, stop_live=handoff_factor * n,
        handoff_input=handoff_input_ok(), pst_h=_pst_after_fetch,
        accumulate_pst_ok=acc_ok, perf=perf)
    obs.event("hybrid.handoff", kind=res[0], rounds=int(res[4]),
              live=int(res[3]),
              **{k: perf[k] for k in ("stream_mode", "fetch_windows",
                                      "handoff_links", "packed_handoff")
                 if k in perf})
    if res[0] == "device":  # converged before the handoff threshold
        _, a, b, live, rounds = res
        _join_prefetch()
        parent = parent_from_links(a, b, n)
        return _finish(fetched.get("seq", seq), fetched.get("m", m), parent,
                       _pst_resolved())
    _, parent_h, pst_out, live, rounds = res
    m = int(fetched.get("m", m))
    seq_np = _as_u32(np.ascontiguousarray(
        np.asarray(fetched.get("seq", seq))[:m]))
    return seq_np, Forest(parent_h[:m].copy(), pst_out[:m].copy())


def handoff_input_ok() -> bool:
    """THE immediate-handoff gate, shared by the hybrid and the
    streaming final fold so the sites can't drift: skip the device
    dedupe rounds only where the d2h copy is free (cpu backend) AND the
    native union-find consumes the undeduped links (the pure-python UF
    pays per link; a byte-bound accelerator fetch wants the dedupe
    rounds to shrink the volume first)."""
    from ..core.forest import native_or_none
    return jax.devices()[0].platform == "cpu" \
        and native_or_none("auto") is not None


def default_handoff_factor() -> int:
    """Platform-tuned handoff threshold (stop_live = factor * n).

    On cpu the "transfer" is free, so hand off as early as possible (8n ~
    after the first dedupe round; measured 3.3x faster than reducing to
    2n).  On a real accelerator the handoff is a device->host copy over
    the link (0.5GB at 2^23 for 8n), so reduce further first.  The
    pure-python fallback pays per link: keep reducing to 2n without the
    native runtime.  Env override: SHEEP_HANDOFF_FACTOR.
    """
    import os

    from ..core.forest import native_or_none
    if native_or_none("auto") is None:
        default = "2"
    else:
        default = "8" if jax.devices()[0].platform == "cpu" else "3"
    return int(os.environ.get("SHEEP_HANDOFF_FACTOR", default))


def pack_handoff(n: int) -> bool:
    """6-byte packing of the window stream's slices (SHEEP_PACK_HANDOFF
    overrides).  Default: pack on accelerators, where the fetch is a
    real transfer, not on cpu; packing needs n < 2^24.
    """
    pack = os.environ.get("SHEEP_PACK_HANDOFF", "")
    if pack == "":
        pack = "0" if jax.devices()[0].platform == "cpu" else "1"
    return pack == "1" and n < (1 << 24)


@functools.partial(jax.jit, static_argnames=("length",))
def _slice_rows(buf, start, length: int):
    """Fixed-length row slice with a DYNAMIC start: one compiled program
    per (buffer shape, length) instead of one per offset, so the
    streamed fetch reuses a single program across all of its slices."""
    return jax.lax.dynamic_slice_in_dim(buf, start, length, 0)


class _WindowStream:
    """Background slice-streamed d2h of a hi-sorted link table, handed
    out in windows (the streaming windowed handoff's transfer side).

    The table (lo, hi) is an immutable device-array pair, sorted by hi,
    with every live link in the first ``live`` slots.  It transfers as
    fixed-length slices of a 6-byte-packed buffer (:func:`pack_handoff`;
    int32 pairs otherwise), grouped into W equal-count windows —
    contiguous count-slices of the sorted table ARE the hi-quantile
    windows (parallel.chunked.hi_window_bounds rule).  The fetch thread
    runs at most :data:`PREFETCH` windows ahead of the fold consumer, so
    resident host memory is O(live/W * PREFETCH), never the full table;
    :meth:`window` hands window k to the fold and frees its slices while
    k+1 keeps streaming underneath.
    """

    #: windows allowed in flight beyond the one being folded (double
    #: buffering: fold k while k+1 lands and k+2 streams)
    PREFETCH = 2

    def __init__(self, lo, hi, n: int, live: int, slice_links: int,
                 windows: int):
        self.packed = pack_handoff(n)
        self.bytes_per_link = 6 if self.packed else 8
        width = int(lo.shape[0])  # pow2-padded
        # the env knob is an arbitrary int: round DOWN to a power of two
        # (floor 512) so slice_len always divides the pow2 width — a
        # non-dividing slice would silently skip tail links (wrong forest)
        slice_links = 1 << max(9, slice_links.bit_length() - 1)
        self.slice_len = min(slice_links, width)
        self.total_slices = min(-(-live // self.slice_len),
                                width // self.slice_len)
        self.done_slices = 0
        self.failed = False
        #: the exception that stopped the fetch thread; window() re-raises it
        self.error: BaseException | None = None
        #: per-slice fetch seconds (obs.trace.timed — the one timing
        #: path); ``busy_s`` below is the derived view
        self._slice_s: list = []
        self._abort = False
        self._slices: list = []
        # one elementwise pack over the padded width: pow2 shapes only,
        # so the compile family stays bounded
        if self.packed:
            from .forest import pack_links_6b
            self._dev = pack_links_6b(lo, hi)
        else:
            self._dev = (lo.astype(jnp.int32), hi.astype(jnp.int32))
        self._cv = threading.Condition()
        self._consumed = -1  # highest window already handed to the fold
        w = max(1, min(windows, self.total_slices))
        self.windows = w
        self._cuts = [(k * self.total_slices) // w for k in range(w + 1)]
        self._thread = threading.Thread(target=obs.bind(self._run),
                                        daemon=True)
        self._thread.start()

    @property
    def busy_s(self) -> float:
        """Thread time actually spent fetching slices (the overlap
        accounting's ``serialized`` fetch term)."""
        return sum(self._slice_s)

    def _window_of(self, i: int) -> int:
        import bisect
        return bisect.bisect_right(self._cuts, i) - 1

    def _wait_turn(self, i: int) -> None:
        with self._cv:
            while (not self._abort
                   and self._window_of(i)
                   > self._consumed + 1 + self.PREFETCH):
                self._cv.wait(0.5)

    def _notify(self) -> None:
        with self._cv:
            self._cv.notify_all()

    def _run(self) -> None:
        try:
            for i in range(self.total_slices):
                self._wait_turn(i)
                if self._abort:
                    return
                start = i * self.slice_len
                with obs.timed("fetch.slice", out=self._slice_s, slice=i):
                    if self.packed:
                        self._slices.append(
                            np.asarray(_slice_rows(self._dev, start,
                                                   self.slice_len)))
                    else:
                        lo_d, hi_d = self._dev
                        self._slices.append(
                            (np.asarray(_slice_rows(lo_d, start,
                                                    self.slice_len)),
                             np.asarray(_slice_rows(hi_d, start,
                                                    self.slice_len))))
                self.done_slices = i + 1
                self._notify()
        except Exception as exc:  # re-raised by window()
            self.error = exc
            self.failed = True
        finally:
            self._dev = None  # release the device buffer promptly
            self._notify()

    def window(self, k: int, timeout_s: float | None = None):
        """Block until window k has fully landed, then return its host
        (lo, hi) int arrays (unfiltered — callers mask lo < n) and free
        the backing slices.  Raises the fetch thread's own exception on
        a failed stream, TimeoutError on a wedged one."""
        lo_w, hi_w = self.collect_range(self._cuts[k], self._cuts[k + 1],
                                        timeout_s)
        with self._cv:
            self._consumed = max(self._consumed, k)
            self._cv.notify_all()
        return lo_w, hi_w

    def collect_range(self, s0: int, s1: int,
                      timeout_s: float | None = None):
        if timeout_s is None:
            # generous watchdog (the range's bytes at 0.5MB/s plus
            # grace): a wedged transfer must never hold the build forever
            timeout_s = ((s1 - s0) * self.slice_len * self.bytes_per_link
                         / 5e5 + 120.0)
        deadline = time.monotonic() + timeout_s
        with self._cv:
            while self.done_slices < s1 and not self.failed:
                left = deadline - time.monotonic()
                if left <= 0:
                    self.failed = True
                    break
                self._cv.wait(min(left, 0.5))
        if self.error is not None:
            raise self.error
        if self.failed:
            raise TimeoutError(
                f"window stream stalled: {self.done_slices} of {s1} "
                f"slices landed within {timeout_s:.0f}s")
        part = self._slices[s0:s1]
        for i in range(s0, s1):  # bound resident memory to the window
            self._slices[i] = None
        if not part:
            return np.empty(0, np.int32), np.empty(0, np.int32)
        if self.packed:
            from .forest import unpack_links_6b
            return unpack_links_6b(np.concatenate(part))
        los, his = zip(*part)
        return np.concatenate(los), np.concatenate(his)

    def abort(self, timeout: float = 5.0) -> None:
        """Stop at the next slice boundary and wait briefly for the
        thread; the landed slices stay a prefix of the table."""
        self._abort = True
        self._notify()
        self._thread.join(timeout)


def handoff_windows(live: int) -> int:
    """Window-count policy (SHEEP_HANDOFF_WINDOWS overrides).  On the
    cpu backend the device->host fetch is a zero-copy view — there is
    nothing to overlap, and the blocked kernel's internal quantile
    bucketing already IS the windowing — so ONE window is optimal.  On a
    real accelerator the fetch is a genuine transfer: 4 windows keep the
    fold busy behind the stream while each window stays large enough to
    amortize its slice dispatches; tiny handoffs stay monolithic."""
    v = os.environ.get("SHEEP_HANDOFF_WINDOWS", "")
    if v != "":
        return max(1, int(v))
    if jax.devices()[0].platform == "cpu":
        return 1
    return 4 if live >= (1 << 20) else 1


def host_seq_mode() -> bool:
    """Host-computed degree sequence for the streaming hybrid
    (SHEEP_STREAM_HOST_SEQ overrides).  Where device and host share the
    silicon (cpu backend), the native counting-sort sequence is ~6x the
    XLA histogram+sort and the device program shrinks to the link
    mapping alone — measured the difference between a ~7.6s and a ~3.5s
    hybrid at 2^22 on the 1-core bench host.  On a real accelerator the
    device sort is cheap and a host sequence would serialize in front of
    the mapping, so default off there."""
    v = os.environ.get("SHEEP_STREAM_HOST_SEQ", "")
    if v != "":
        return v == "1"
    return jax.devices()[0].platform == "cpu"


def _as_u32(a: np.ndarray) -> np.ndarray:
    """uint32 without a copy where possible: contiguous int32 (the fetch
    dtype) reinterprets for free — exact under the package-wide
    nonnegative-int32 value contract — instead of the unconditional
    .astype() that used to copy multi-hundred-MB link arrays through the
    handoff path."""
    a = np.asarray(a)
    if a.dtype == np.uint32:
        return a
    if a.dtype == np.int32 and a.flags["C_CONTIGUOUS"]:
        return a.view(np.uint32)
    return a.astype(np.uint32, copy=False)


def _stream_tail(lo, hi, live: int, n: int, pst_h, accumulate: bool,
                 perf: dict | None):
    """The streaming windowed handoff tail: fetch the reduced live set
    as W ascending hi-quantile windows and fold each straight into the
    resumable union-find.  Returns (parent, pst) uint32 [n]; a failed
    fetch or fold raises.

    ``accumulate`` True means the windows together carry the ORIGINAL
    link multiset (immediate handoff, zero reduce rounds) and pst is
    counted inside the fold's own read pass — the device/host pst
    resolver ``pst_h`` is then never touched.  False: ``pst_h`` (array
    or zero-arg callable) resolves AFTER the stream has started, so a
    caller's pst prefetch keeps overlapping the first window's fetch.
    """
    from ..core.forest import host_hi_window_bounds, links_fold

    t_start = time.perf_counter()
    w = handoff_windows(int(live))
    platform = jax.devices()[0].platform
    # SHEEP_STREAM_DEVICE_WINDOWS=1 forces the accelerator transfer path
    # (device hi-sort + _WindowStream slices) on the cpu backend, so the
    # window-queue machinery is exercised without hardware
    device_windows = platform != "cpu" \
        or os.environ.get("SHEEP_STREAM_DEVICE_WINDOWS", "") == "1"
    stream = None
    fetch_s: list[float] = []
    fold_s: list[float] = []
    links_folded = 0
    try:
        if device_windows:
            # device-side windowing: ONE hi-sort program, then windows
            # are contiguous equal-count slices streamed double-buffered
            slo, shi = _sort_by_hi_prog(lo, hi)
            slice_links = int(os.environ.get("SHEEP_OVERLAP_SLICE",
                                             str(1 << 18)))
            stream = _WindowStream(slo, shi, n, int(live), slice_links, w)
            w = stream.windows

            def windows_iter():
                for k in range(w):
                    yield stream.window(k)
        else:
            # cpu backend: the "fetch" is a zero-copy view (it blocks on
            # the async device program — that wait IS the old fetch_tail
            # wall); windows split host-side by the shared quantile rule
            def windows_iter():
                lo_h = np.asarray(lo)[:int(live)]
                hi_h = np.asarray(hi)[:int(live)]
                keep = lo_h < n
                if w == 1:
                    yield lo_h[keep], hi_h[keep]
                    return
                lo_k = lo_h[keep]
                hi_k = hi_h[keep]
                bounds = host_hi_window_bounds(hi_k[hi_k < n], w, n)
                for k in range(w):
                    sel = hi_k >= bounds[k]
                    if k + 1 < w:  # last window keeps any pst-only tail
                        sel &= hi_k < bounds[k + 1]
                    yield lo_k[sel], hi_k[sel]

        it = windows_iter()
        pst_arr = None
        if not accumulate:
            pst_arr = _as_u32(pst_h() if callable(pst_h) else pst_h)
        fold = links_fold(n, pst_arr)
        # one accumulation path for the fetch/fold pairs (obs.trace.timed
        # — spans when SHEEP_TRACE is on, the same measured series either
        # way); the perf keys below are derived views of these lists
        for k in range(w):
            with obs.timed("handoff.fetch", out=fetch_s, window=k):
                wlo, whi = next(it)
                keep = wlo < n
                if not keep.all():
                    wlo, whi = wlo[keep], whi[keep]
            with obs.timed("handoff.fold", out=fold_s, window=k,
                           links=len(wlo)):
                fold.block(_as_u32(wlo), _as_u32(whi))
            links_folded += len(wlo)
        parent, pst_out = fold.finish()
    except BaseException:
        # stop the fetch thread before the error leaves the tail
        if stream is not None:
            stream.abort()
        raise
    if perf is not None:
        wall = time.perf_counter() - t_start
        fetch_busy = stream.busy_s if stream is not None else sum(fetch_s)
        from ..core.forest import native_or_none
        native = native_or_none("auto")
        perf.update({
            "stream_mode": "windowed",
            "fetch_windows": w,
            "window_fetch_s": [round(x, 4) for x in fetch_s],
            "window_fold_s": [round(x, 4) for x in fold_s],
            "fold_s": round(sum(fold_s), 4),
            # THE shared overlap accounting (obs.trace.overlap_stats)
            **obs.overlap_stats(fetch_busy + sum(fold_s), wall),
            "handoff_links": links_folded,
            "packed_handoff": stream.packed if stream is not None
            else False,
            # worker threads under the fold (round 14): >1 means the
            # windows folded on real parallel cores while the fetch ran
            # ahead — the knob that makes the overlap real off 1 core
            "native_threads": native.resolve_threads()
            if native is not None else 1,
        })
    return parent, pst_out


@functools.partial(jax.jit)
def _sort_by_hi_prog(lo, hi):
    """Cached program wrapper of ops.forest.sort_links_by_hi (one compile
    per table shape)."""
    from .forest import sort_links_by_hi
    return sort_links_by_hi(lo, hi)


def reduce_and_finish_native(lo, hi, n: int, stop_live: int,
                             handoff_input: bool = False, pst_h=None,
                             accumulate_pst_ok: bool = False, perf=None):
    """THE production reduce + handoff + native-tail of the hybrid,
    shared with ops.stream's final fold so the two cannot drift.

    Chunk rounds to ``stop_live``, then :func:`_stream_tail`: W
    ascending hi-quantile windows, each folded through the resumable
    native union-find the moment it lands, fold k overlapping fetch k+1,
    the full link table never host-resident.

    Returns ("device", lo, hi, live, rounds) when the reduce loop
    converged before the handoff threshold (the links already form the
    forest — no native tail ran), else ("forest", parent, pst, live,
    rounds) with parent/pst uint32 [n].

    ``pst_h`` — array or zero-arg callable resolving the prep-time pst;
    consulted only when the fold cannot count pst itself.
    ``accumulate_pst_ok`` — the caller vouches the INPUT links are the
    original multiset with no pst-only record masked out (full-graph
    prep, or an internally derived full-coverage sequence); the fold
    then accumulates pst in its own read pass whenever the loop took the
    immediate-handoff exit (zero rounds — any chunk round rewrites the
    multiset, after which only the prep-time pst is right).

    ``perf`` gains loop_s and fetch_tail_s — fetch_tail_s is the
    whole tail wall (fetch + fold minus their overlap) — plus the
    per-window breakdown (fetch_windows, window_fetch_s / window_fold_s,
    overlap_s / overlap_frac, stream_mode) and handoff_links.
    """
    from .forest import reduce_links_hosted

    t0 = time.perf_counter()
    with obs.span("reduce.loop", stop_live=stop_live):
        lo, hi, live, rounds, converged = reduce_links_hosted(
            lo, hi, n, stop_live=stop_live, handoff_input=handoff_input)
    t1 = time.perf_counter()
    if perf is not None:
        perf["loop_s"] = round(t1 - t0, 4)
    if converged:
        if perf is not None:
            perf["fetch_tail_s"] = 0.0
        return "device", lo, hi, int(live), rounds
    accumulate = accumulate_pst_ok and rounds == 0
    parent, pst = _stream_tail(lo, hi, int(live), n, pst_h, accumulate,
                               perf)
    if perf is not None:
        perf["fetch_tail_s"] = round(time.perf_counter() - t1, 4)
    return "forest", parent, pst, int(live), rounds
