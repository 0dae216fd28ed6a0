"""Seconds per graph2tree call loading the edge file: the flight
recorder's ``io.load`` spans (io/edges.py load_edges), summed per call."""

from spanread import per_call, span_sum


def read(obs):
    return per_call(obs.tree_records, span_sum("io.load"))
