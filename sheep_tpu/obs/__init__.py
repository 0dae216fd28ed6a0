"""Flight recorder (ISSUE 10): one measurement substrate for every rung.

Two halves, deliberately dependency-free so the jax-free paths (serve/,
ops/extmem) can import them without dragging a backend in:

  obs.trace    hierarchical spans + events appended crash-safely to a
               JSONL file named by ``SHEEP_TRACE`` (unset = disabled at
               ~zero cost), wired through the whole build path: ladder
               decisions, chunk rounds, windowed-handoff fetch/fold
               pairs, ext-block read/fold, native kernel calls,
               checkpoint/WAL fsyncs, fault firings.  The per-phase
               rollup and the shared overlap accounting
               (:func:`~sheep_tpu.obs.trace.overlap_stats`) replace the
               three ad-hoc timing systems that grew before it
               (SHEEP_NATIVE_TIME stderr timers, the hand-built perf
               dicts, prefetch ``busy_s``) — the old record keys remain
               as derived views of the one code path.
  obs.metrics  a tiny counters/gauges/fixed-bucket-histogram registry
               (no deps) the serve daemon exports over the wire
               (``METRICS`` verb, Prometheus text format) and summarizes
               into ``STATS`` (per-verb counts + p50/p99).

``sheep trace`` (cli/trace.py) renders a trace file: per-phase rollup,
the ladder-rung decision explanation (governor price vs measured), and a
text timeline — the precursor of the planner's ``plan --explain``.
"""

from .metrics import (Counter, Gauge, Histogram, Registry,
                      parse_prometheus, proc_status, relabel,
                      set_process_gauges)
from .trace import (bind, current_rid, enabled, event, new_rid, overlap_stats,
                    read_trace, read_trace_chain, repair_trace, rid_scope,
                    rollup, span, timed, trace_segments, trace_summary)

__all__ = [
    "Counter", "Gauge", "Histogram", "Registry",
    "parse_prometheus", "proc_status", "relabel", "set_process_gauges",
    "bind", "current_rid", "enabled", "event", "new_rid", "overlap_stats",
    "read_trace", "read_trace_chain", "repair_trace", "rid_scope",
    "rollup", "span", "timed", "trace_segments", "trace_summary",
]
