"""A/B chunk-schedule variants for the hybrid's reduce phase.

HISTORY: the 2026-07-30 run of this script (variants base / nosort1 /
lvl2, at 2^18 and 2^20 on the cpu backend) motivated the jump-only
opener that now runs INSIDE reduce_links_hosted — nosort1 measured
26-39% faster to the hybrid handoff and was productized.  The variants
below reflect the post-opener world:

  prod     — current reduce_links_hosted (opener + sorted schedule)
  dblopen  — an EXTRA jump-only round before the production path (tests
             whether a second sort-free round pays)
  lvl2     — first_levels=2 (cheaper full-size rounds; rejected once,
             kept here for re-testing on other backends)

For each, measures wall time and rounds to the hybrid stop (live <=
3n) and to full convergence, at one size.  Usage:
  python scripts/sched_ab.py LOG_N [reps]
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from sheep_tpu.utils import rmat_edges


def main() -> None:
    log_n = int(sys.argv[1]) if len(sys.argv) > 1 else 18
    reps = int(sys.argv[2]) if len(sys.argv) > 2 else 2
    n = 1 << log_n

    from sheep_tpu.cli.common import init_compile_cache
    init_compile_cache()
    import jax
    import jax.numpy as jnp
    from sheep_tpu.ops.build import prepare_links
    from sheep_tpu.ops import forest as F

    tail, head = rmat_edges(log_n, 8 << log_n, seed=1)
    t = jax.device_put(jnp.asarray(tail, jnp.int32))
    h = jax.device_put(jnp.asarray(head, jnp.int32))
    _, _, _, lo0, hi0, _ = prepare_links(t, h, n)
    lo0.block_until_ready()

    def reduce_with(variant, stop_live):
        lo, hi = lo0, hi0
        rounds = 0
        if variant == "dblopen":
            lo, hi, _ = F.jump_chunk(lo, hi, n, 4)
            rounds += 1
        lo, hi, live, r, conv = F.reduce_links_hosted(
            lo, hi, n, stop_live=stop_live,
            first_levels=2 if variant == "lvl2" else 4)
        return rounds + r, live, conv

    results = {}
    for name in ("prod", "dblopen", "lvl2"):
        for stop, label in ((3 * n, "handoff"), (0, "converge")):
            best = None
            rr = ll = None
            for _ in range(reps + 1):  # +1 warmup/compile
                t0 = time.perf_counter()
                rr, ll, _ = reduce_with(name, stop)
                dt = time.perf_counter() - t0
                best = dt if best is None else min(best, dt)
            results[f"{name}_{label}"] = {
                "s": round(best, 3), "rounds": rr, "live": ll}
            print(name, label, results[f"{name}_{label}"], flush=True)


if __name__ == "__main__":
    main()
