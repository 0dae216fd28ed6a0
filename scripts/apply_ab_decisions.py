"""Read the round-5 on-chip A/B artifacts and print each pinned decision.

The decision rules live in PERF_NOTES.md ("Round-5 notes" + the round-4
pending rules); this script encodes them so applying a window's results
is mechanical and auditable.  It ONLY reports — flipping a default stays
a reviewed code change.

Arms (all in TPU_AB_r05.jsonl unless noted; baseline = profile_20 in
TPU_PROFILE_r05.jsonl, the default-config profile at 2^20):
  ab_overlap_off   SHEEP_OVERLAP_HANDOFF=0   -> overlap default
  ab_pipeline_off  SHEEP_PIPELINE_CHUNKS=0   -> pipelined dispatch default
  ab_sort_pack64   SHEEP_SORT_PACK64=1       -> accelerator pack64 default
  ab_pack_off      SHEEP_PACK_HANDOFF=0 (+overlap off; comparator is
                   ab_overlap_off, NOT the baseline)
  ab_handoff_1/8   factor arms               -> accelerator handoff factor

Usage: python scripts/apply_ab_decisions.py
"""

from __future__ import annotations

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _records(path: str) -> list[dict]:
    out = []
    try:
        with open(os.path.join(REPO, path)) as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if isinstance(rec, dict):
                    out.append(rec)
    except OSError:
        pass
    return out


def _arm(records: list[dict], step: str) -> dict | None:
    # accel-only: a cpu record must read as NO DATA, never as an
    # on-chip verdict
    hits = [r for r in records if r.get("_step") == step
            and not r.get("_partial") and r.get("platform") != "cpu"]
    return hits[-1] if hits else None


def _speed(rec: dict | None) -> float | None:
    if rec is None:
        return None
    # best-of-reps total when present; the single total otherwise
    totals = rec.get("totals") or ([rec["total"]] if "total" in rec else [])
    return min(totals) if totals else None


def main() -> None:
    abs_recs = _records("TPU_AB_r05.jsonl")
    profiles = _records("TPU_PROFILE_r05.jsonl")
    base = _arm(profiles, "profile_20")
    base_s = _speed(base)
    decisions = []

    def rule(name: str, arm_rec, comparator_s, flip_if_faster_by: float,
             keep_msg: str, flip_msg: str):
        s = _speed(arm_rec)
        if s is None or comparator_s is None:
            decisions.append((name, "NO DATA — step not yet run on-chip"))
            return
        ratio = comparator_s / s  # >1: the arm is faster
        verdict = flip_msg if ratio > flip_if_faster_by else keep_msg
        decisions.append(
            (name, f"arm {s:.2f}s vs comparator {comparator_s:.2f}s "
                   f"(arm {ratio:.2f}x) -> {verdict}"))

    overlap_off = _arm(abs_recs, "ab_overlap_off")
    rule("overlap (SHEEP_OVERLAP_HANDOFF)", overlap_off, base_s, 1.10,
         "KEEP default-on", "FLIP to off — off arm >10% faster")
    rule("pipelined dispatch (SHEEP_PIPELINE_CHUNKS)",
         _arm(abs_recs, "ab_pipeline_off"), base_s, 1.10,
         "KEEP default-on", "FLIP to off — off arm >10% faster")
    rule("accelerator pack64 sort (ops.forest._pack64_sorts)",
         _arm(abs_recs, "ab_sort_pack64"), base_s, 1.0,
         "keep accelerator default OFF", "FLIP accelerator default ON")
    rule("6-byte handoff packing (SHEEP_PACK_HANDOFF, overlap-off regime)",
         _arm(abs_recs, "ab_pack_off"), _speed(overlap_off), 1.0,
         "keep default-on (helps when byte-bound; comparator ab_overlap_off)",
         "pack-off faster — consider default-off for fat links")
    for arm, label in (("ab_handoff_1", "factor 1"),
                       ("ab_handoff_8", "factor 8")):
        # pinned rule is margin-free: "ab_handoff_1 beats factor 3 ->
        # change the accelerator default" (PERF_NOTES round-4 rules)
        rule(f"handoff {label} (default_handoff_factor accel=3)",
             _arm(abs_recs, arm), base_s, 1.0,
             "keep accel factor 3", f"FLIP accel default to {label[-1]}")

    width = max(len(n) for n, _ in decisions)
    for name, verdict in decisions:
        print(f"{name:<{width}}  {verdict}")
    # VERDICT r04 item-1 done gate: total <= 2x reduce at BOTH 2^20 and
    # 2^22 (PERF_NOTES round-5 rules)
    for step in ("profile_20", "profile_22"):
        p = _arm(profiles, step)
        if p is None:
            print(f"\n{step}: NO DATA — not yet run on-chip")
            continue
        spec = {k: p.get(k) for k in
                ("spec_mode", "spec_starts", "spec_restarts",
                 "spec_wasted_mb", "spec_stopped_loop")}
        print(f"\n{step}: total={p.get('total')}s "
              f"reduce={p.get('reduce')}s d2h={p.get('d2h')}s spec={spec}")
        if p.get("total") and p.get("reduce"):
            gate = p["total"] <= 2 * p["reduce"]
            print(f"item-1 gate at {step} (total <= 2x reduce): "
                  f"{'MET' if gate else 'NOT MET'} "
                  f"({p['total']:.2f} vs 2x{p['reduce']:.2f})")


if __name__ == "__main__":
    main()
