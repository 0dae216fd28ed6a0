"""Seconds per partition_tree call in the evaluator's part-set bitmap
scan: the flight recorder's ``evaluate.scan`` spans
(partition/evaluate.py), summed per call.  Silent on a call that
evaluated with the sort body, which has no such span."""

from spanread import per_call, span_sum


def read(obs):
    return per_call(obs.partition_records, span_sum("evaluate.scan"))
