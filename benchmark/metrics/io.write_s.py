"""Seconds per graph2tree call writing the .seq and the .tre: the flight
recorder's ``io.write`` spans (io/seqfile.py, io/trefile.py), summed per
call."""

from spanread import per_call, span_sum


def read(obs):
    return per_call(obs.tree_records, span_sum("io.write"))
