"""The comparison that decides ``correct`` for a training cell.

The program's first steps, as its timed path ran them, against the plain
reference following the same steps from the same weights and batches:

- ``loss_gap_<i>``: |program loss - reference loss| at step i, in nats.
- ``grad_norm_gap``: the first gradient as the optimizer took it (after
  clipping), by the worst leaf: |norm(program leaf) - norm(reference leaf)|
  over the larger of the reference leaf's norm and the median leaf's.
- ``update_norm_gap``: the same for the parameters' change over all the
  steps, leaving out leaves whose reference gradient is under a thousandth
  of the median leaf's (they move under Adam by rounding alone).
- ``kernel_fallbacks``: kernel-path fallbacks the program counted.

Each number has an entry in the workload file's ``limits``: a limit, or
null for a number that is printed as a reading and not compared (PERF.md
gives the reason for each). A run is correct when every compared number is
at or under its limit; a number with no entry fails.
"""
from __future__ import annotations

import statistics

# reference gradient under this share of the median leaf's: leaf left out
# of the change comparison
STILL_LEAF = 1e-3


def worst_leaf_gap(prog, ref, keep=None) -> float:
    keep = keep or [True] * len(ref)
    kept = [r for r, k in zip(ref, keep) if k]
    floor = statistics.median(kept)
    return max(abs(p - r) / max(r, floor)
               for p, r, k in zip(prog, ref, keep) if k)


def train_numbers(prog: dict, ref: dict) -> dict:
    out = {f"loss_gap_{i}": abs(p - r) for i, (p, r) in
           enumerate(zip(prog["losses"], ref["losses"]), 1)}
    out["grad_norm_gap"] = worst_leaf_gap(prog["grad_norms"],
                                          ref["grad_norms"])
    floor = STILL_LEAF * statistics.median(ref["grad_norms"])
    keep = [g >= floor for g in ref["grad_norms"]]
    out["update_norm_gap"] = worst_leaf_gap(prog["change_norms"],
                                            ref["change_norms"], keep)
    return out


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value": number, "limit": limit}})."""
    checks = {k: {"value": v, "limit": limits.get(k)}
              for k, v in numbers.items()}
    ok = all(k in limits and (c["limit"] is None or c["value"] <= c["limit"])
             for k, c in checks.items())
    return ok, checks
