"""Retry-with-backoff for device dispatches, with adaptive shrinking.

The chunk drivers' unit of failure is one bounded dispatch (a
``fixpoint_chunk`` / ``chunk_sharded`` call).  A dispatch that faults
because its wall time outgrew a per-execution budget does so in
proportion to its round count, so the right retry is not "same thing
again" but SHRINK: halve
``jrounds`` before re-dispatching, so a dispatch that tripped the budget
asks for half the work next time.  Progress already made is never lost
(dispatches are functional — the inputs are intact after a fault), and a
1-round dispatch is the minimum quantum, so shrinking terminates.

Exponential backoff between attempts covers the transient-infrastructure
case (preempted worker, transient runtime error): sleeping ``base * 2^attempt``
capped at ``cap``.  A watchdog (optional) bounds how long a HUNG dispatch
can stall the build: block_until_ready runs on a helper thread and a
timeout classifies the dispatch as faulted (DeadlineExceeded, retryable —
the stuck execution is abandoned to the backend).
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Callable

from .faults import (DeadlineExceeded, RetryBudgetExhausted, fault_point,
                     is_retryable)


@dataclass
class RetryPolicy:
    """Knobs for one build's dispatch retries (CLI: --max-retries; env:
    SHEEP_MAX_RETRIES / SHEEP_BACKOFF_BASE / SHEEP_WATCHDOG_S)."""

    max_retries: int = 3
    backoff_base_s: float = 0.05
    backoff_cap_s: float = 2.0
    shrink: bool = True
    watchdog_s: float | None = None
    # injectable for tests (no real sleeping in the suite)
    sleep: Callable[[float], None] = field(default=time.sleep)

    def backoff(self, attempt: int) -> float:
        return min(self.backoff_base_s * (2 ** attempt), self.backoff_cap_s)


def call_with_watchdog(fn, j, timeout_s: float | None):
    """``fn(j)`` + block_until_ready under a deadline (None = unbounded).

    The whole attempt runs on a helper thread: dispatch itself can block
    too (compilation, a wedged runtime), not just the result wait.  On
    timeout the attempt is abandoned to the backend and classified as a
    retryable :class:`DeadlineExceeded`.
    """
    import jax

    if timeout_s is None:
        out = fn(j)
        jax.block_until_ready(out)
        return out
    done = threading.Event()
    result: dict = {}

    def attempt():
        try:
            out = fn(j)
            jax.block_until_ready(out)
            result["out"] = out
        except BaseException as exc:  # surfaced on the caller thread
            result["err"] = exc
        finally:
            done.set()

    t = threading.Thread(target=attempt, daemon=True)
    t.start()
    if not done.wait(timeout_s):
        raise DeadlineExceeded(
            f"dispatch still not ready after {timeout_s}s watchdog")
    if "err" in result:
        raise result["err"]
    return result["out"]


def run_with_retry(policy: RetryPolicy, site: str,
                   fn: Callable, j: int | None,
                   on_retry: Callable[[str, int, int | None], None]
                   | None = None):
    """Run ``fn(j)`` (a dispatch returning device outputs), blocking until
    ready; on a retryable failure, back off, halve ``j`` (when shrinking
    applies and ``j`` is not None), and retry up to the budget.

    Each ATTEMPT passes through :func:`faults.fault_point` under ``site``
    — that is the deterministic injection hook.  Returns
    ``(outputs, j_used)``.  Raises :class:`RetryBudgetExhausted` once the
    budget is spent (the degradation ladder's cue), and re-raises
    non-retryable exceptions (including BuildKilled) untouched.
    """
    attempt = 0
    while True:
        try:
            fault_point(site)
            return call_with_watchdog(fn, j, policy.watchdog_s), j
        except BaseException as exc:
            if not is_retryable(exc):
                raise
            if attempt >= policy.max_retries:
                raise RetryBudgetExhausted(
                    f"{site}: {attempt + 1} attempts all faulted "
                    f"(last: {type(exc).__name__}: {exc})") from exc
            policy.sleep(policy.backoff(attempt))
            if policy.shrink and j is not None:
                j = max(1, j // 2)
            attempt += 1
            if on_retry is not None:
                on_retry(site, attempt, j)
