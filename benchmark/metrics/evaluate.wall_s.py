"""Seconds per partition_tree call evaluating the partitions: the flight
recorder's ``partition.evaluate`` spans (partition/evaluate.py), summed
per call."""

from spanread import per_call, span_sum


def read(obs):
    return per_call(obs.partition_records, span_sum("partition.evaluate"))
