"""Seconds per graph2tree call that no layer span explains: the
``cli.graph2tree`` span minus the union of its direct children."""

from spanread import per_call, unexplained


def read(obs):
    return per_call(obs.tree_records, unexplained("cli.graph2tree"))
