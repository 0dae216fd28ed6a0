"""Deterministic fault injection + the runtime's exception taxonomy.

Every recovery path in the fault-tolerant build runtime (sheep_tpu.runtime)
must be testable on CPU, where real dispatch faults never happen.  This
module provides the hook: the chunk drivers call :func:`fault_point` at
every dispatch attempt and every checkpointed chunk boundary, and an
installed :class:`FaultPlan` (monkeypatchable via :func:`install_plan`, or
env-configured via ``SHEEP_FAULT_INJECT`` — the same spirit as the watcher's
gating tests, tests/test_watcher.py) kills exactly the k-th call at a named
site.  Sites are counted per build (:func:`reset_counters`), so "kill
dispatch 3" means the same dispatch on every run — which is what makes the
kill-at-every-chunk-boundary resume property test possible.

Fault kinds model three failure shapes of a device build:

  xla       a faulted dispatch (the per-execution budget trip) — retryable,
            surfaces as :class:`InjectedDispatchFault`, classified together
            with the backend's real ``XlaRuntimeError``.
  deadline  a hung dispatch caught by the watchdog — retryable.
  kill      SIGKILL / OOM-killer: the process dies mid-build.  Raised as
            :class:`BuildKilled`, which nothing in the runtime catches —
            recovery is a NEW process resuming from the checkpoint.
"""

from __future__ import annotations

import os
from dataclasses import dataclass


class InjectedDispatchFault(RuntimeError):
    """A deliberately faulted device dispatch (kind="xla")."""


class DeadlineExceeded(RuntimeError):
    """A dispatch exceeded the watchdog budget (real or injected)."""


class BuildKilled(RuntimeError):
    """Simulated process death (kind="kill").  Never caught by the retry
    wrapper or the degradation ladder: tests catch it at top level and
    then resume from the checkpoint, exactly like a restarted process."""


class RetryBudgetExhausted(RuntimeError):
    """A dispatch site kept faulting past the retry budget; the ladder
    degrades to the next rung on this."""


@dataclass
class FaultPlan:
    """Kill the ``at``-th (0-based) matching call — and the ``times - 1``
    following matching calls — at ``site``.

    ``site``: comma-separated site names, or "*" for every site.
    ``times``: -1 means "every matching call from ``at`` on" (used to force
    a rung to exhaust its retry budget and trigger ladder degradation).
    """

    site: str
    at: int
    kind: str = "xla"
    times: int = 1

    def matches(self, site: str, index: int) -> bool:
        if self.site != "*" and site not in self.site.split(","):
            return False
        if index < self.at:
            return False
        return self.times < 0 or index < self.at + self.times

    def raise_fault(self, site: str, index: int) -> None:
        msg = f"injected {self.kind} fault at {site}[{index}]"
        if self.kind == "kill":
            raise BuildKilled(msg)
        if self.kind == "deadline":
            raise DeadlineExceeded(msg)
        raise InjectedDispatchFault(msg)


_plan: FaultPlan | None = None
_counters: dict[str, int] = {}


def install_plan(plan: FaultPlan | None) -> None:
    """Install (or with None, clear) the active fault plan."""
    global _plan
    _plan = plan


def clear_plan() -> None:
    install_plan(None)


def active_plan() -> FaultPlan | None:
    """The installed plan, falling back to ``SHEEP_FAULT_INJECT`` —
    format ``site:at[:kind[:times]]``, e.g. ``chunk:3:xla:2`` or
    ``boundary:1:kill``."""
    if _plan is not None:
        return _plan
    spec = os.environ.get("SHEEP_FAULT_INJECT", "")
    if not spec:
        return None
    return parse_plan(spec)


def parse_plan(spec: str) -> FaultPlan:
    parts = spec.split(":")
    if len(parts) < 2:
        raise ValueError(
            f"SHEEP_FAULT_INJECT={spec!r}: want site:at[:kind[:times]]")
    site, at = parts[0], int(parts[1])
    kind = parts[2] if len(parts) > 2 else "xla"
    times = int(parts[3]) if len(parts) > 3 else 1
    if kind not in ("xla", "deadline", "kill"):
        raise ValueError(f"unknown fault kind {kind!r}")
    return FaultPlan(site=site, at=at, kind=kind, times=times)


def reset_counters() -> None:
    """Start a fresh build: site indices count from 0 again."""
    _counters.clear()


def fault_count(site: str) -> int:
    """How many times ``site`` has fired since the last reset."""
    return _counters.get(site, 0)


def fault_point(site: str) -> int:
    """Record one call at ``site`` and raise if the active plan kills it.
    Returns this call's 0-based index (useful for logging)."""
    index = _counters.get(site, 0)
    _counters[site] = index + 1
    plan = active_plan()
    if plan is not None and plan.matches(site, index):
        from ..obs import trace as obs
        obs.event("fault", site=site, index=index, kind=plan.kind)
        plan.raise_fault(site, index)
    return index


def is_retryable(exc: BaseException) -> bool:
    """Classify a dispatch failure: True = retry/degrade territory, False =
    programming error or simulated process death (propagate).

    Real backend faults arrive as ``jaxlib...XlaRuntimeError`` (also the
    base of jax's ResourceExhausted/Internal errors); matching by class
    name keeps this working across jaxlib layouts without importing
    private modules.
    """
    if isinstance(exc, BuildKilled):
        return False
    if isinstance(exc, (InjectedDispatchFault, DeadlineExceeded)):
        return True
    for klass in type(exc).__mro__:
        if klass.__name__ in ("XlaRuntimeError", "JaxRuntimeError"):
            return True
    return False
