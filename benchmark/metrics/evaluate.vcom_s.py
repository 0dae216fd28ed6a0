"""Seconds per partition_tree call in the in-memory evaluator's
Vcom step (the distinct parts beside each vertex): the flight
recorder's ``evaluate.vcom`` spans
(partition/evaluate.py), summed per call."""

from spanread import per_call, span_sum


def read(obs):
    return per_call(obs.partition_records, span_sum("evaluate.vcom"))
