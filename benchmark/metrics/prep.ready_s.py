"""Seconds per graph2tree call from the dispatch of ``prepare_links``
(histogram, degree sort, link map) to its output being ready on the
device: the flight recorder's ``prep`` spans (ops/build.py), summed per
call."""

from spanread import per_call, span_sum


def read(obs):
    return per_call(obs.tree_records, span_sum("prep"))
