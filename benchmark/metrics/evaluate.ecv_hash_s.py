"""Seconds per partition_tree call in the in-memory evaluator's
ECV(hash) step, with the hash balance: the flight recorder's
``evaluate.ecv_hash`` spans
(partition/evaluate.py), summed per call."""

from spanread import per_call, span_sum


def read(obs):
    return per_call(obs.partition_records, span_sum("evaluate.ecv_hash"))
