"""Seconds per graph2tree call folding the handed-off links into the
native union-find: the flight recorder's ``handoff.fold`` spans
(ops/build.py, one per window of the streamed tail), summed per call."""

from spanread import per_call, span_sum


def read(obs):
    return per_call(obs.tree_records, span_sum("handoff.fold"))
