"""Seconds per partition_tree call that no layer span explains: the
``cli.partition_tree`` span minus the union of its direct children."""

from spanread import per_call, unexplained


def read(obs):
    return per_call(obs.partition_records, unexplained("cli.partition_tree"))
