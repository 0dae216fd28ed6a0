"""What the span metrics read from the flight recorder's records.

A traced window gives the metric readers one list of records per call
(``Observed.tree_records`` / ``partition_records``).  Each helper here
takes such lists; a call whose records hold no span of the name asked
for gives nothing, and a window in which no call gives anything gives
None, so a reader falls silent on a program that lacks the span.
"""

from __future__ import annotations


def spans(records: list[dict], name: str) -> list[dict]:
    return [r for r in records
            if r.get("k") == "span" and r.get("name") == name]


def per_call(calls: list[list[dict]], value) -> float | None:
    """The mean over the calls of ``value(records)``, leaving out the
    calls for which it is None; None when every call is left out."""
    got = [v for v in map(value, calls) if v is not None]
    return sum(got) / len(got) if got else None


def span_sum(name: str, of=lambda r: r["dur"]):
    """A ``value`` for :func:`per_call`: ``of`` summed over the call's
    spans of ``name`` (their seconds by default); None without one."""
    def value(records):
        mine = spans(records, name)
        return sum(of(r) for r in mine) if mine else None
    return value


def unexplained(root: str):
    """A ``value`` for :func:`per_call`: the seconds of the call's
    ``root`` spans that the union of their direct children on the root's
    own thread leaves uncovered.  A child on another thread (a bound
    prefetch or waiter thread) runs beside the root's thread and explains
    none of its time."""
    def value(records):
        kids: dict = {}
        for r in records:
            if r.get("k") == "span" and r.get("par") is not None:
                kids.setdefault((r["par"], r["tid"]), []).append(
                    (r["t"], r["t"] + r["dur"]))
        roots = spans(records, root)
        total = 0.0
        for r in roots:
            lo, hi = r["t"], r["t"] + r["dur"]
            covered, end = 0.0, lo
            for a, b in sorted(kids.get((r["id"], r["tid"]), [])):
                a, b = max(a, end), min(b, hi)
                if b > a:
                    covered += b - a
                    end = b
            total += r["dur"] - covered
        return total if roots else None
    return value
