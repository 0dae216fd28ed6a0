"""Seconds per partition_tree call in the in-memory evaluator's
gathers of each record's parts and the doubling of its keys:
the flight recorder's ``evaluate.gather`` spans
(partition/evaluate.py), summed per call."""

from spanread import per_call, span_sum


def read(obs):
    return per_call(obs.partition_records, span_sum("evaluate.gather"))
