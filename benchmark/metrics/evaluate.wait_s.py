"""Seconds per partition_tree call in which the evaluation waited: each
``partition.evaluate`` span's duration minus its ``cpu`` (the thread's
CPU time over the span), summed per call.  The two clocks tick apart, so
a span whose CPU time reads above its duration waited for nothing."""

from spanread import per_call, span_sum


def read(obs):
    return per_call(obs.partition_records,
                    span_sum("partition.evaluate",
                             of=lambda r: max(0.0, r["dur"] - r["cpu"])))
