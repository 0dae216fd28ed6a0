"""Threads the evaluator's part-set bitmap scan ran on, per
partition_tree call: the ``threads`` attribute of the flight recorder's
``evaluate.scan`` spans (partition/evaluate.py), the count the native
kernel reports it used.  Silent on a program whose scan span carries no
such attribute."""

from spanread import per_call, spans


def _threads(records):
    got = [r["a"]["threads"] for r in spans(records, "evaluate.scan")
           if "threads" in r.get("a", {})]
    return max(got) if got else None


def read(obs):
    return per_call(obs.partition_records, _threads)
