"""The comparison that decides `correct`: the numbers the timed path
produced against the plain reference's, each under a limit of its own. The
limits are data in the cell's file (`limits`), set from readings on the chip
as PERF.md records; a limit that is missing fails the run.
"""
from __future__ import annotations

import math
import statistics
from typing import Any, Dict, List

Checks = Dict[str, Dict[str, Any]]


NOT_COMPARED = "not_compared"


def _check(checks: Checks, name: str, value: float, limits: Dict[str, Any]
           ) -> None:
    """A limit is looked up by the number's full name, then by the part
    before the dot. The word `not_compared` marks a number that has no upper
    reading (PERF.md says why): it is printed and judged by nothing. A limit
    that is missing (written as null, without `compared`) fails."""
    limit = limits.get(name, limits.get(name.split(".")[0]))
    finite = math.isfinite(value)
    # a number that is not finite is written as null: the line stays JSON
    shown = float(value) if finite else None
    if limit == NOT_COMPARED:
        checks[name] = {"value": shown, "limit": None, "ok": finite,
                        "compared": False}
        return
    checks[name] = {"value": shown,
                    "limit": None if limit is None else float(limit),
                    "ok": bool(limit is not None and finite
                               and value <= limit)}


def exact(checks: Checks, name: str, count: int) -> None:
    """A count that has to be nought: the limit is 0."""
    checks[name] = {"value": float(count), "limit": 0.0, "ok": count == 0}


def norm_gap(prog: Dict[str, float], ref: Dict[str, float],
             leaves: List[str]) -> Dict[str, float]:
    """For each leaf the gap between the program's norm and the
    reference's (not the norm of their difference), against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger: some gradients are all but zero."""
    med = statistics.median(ref[k] for k in leaves)
    return {k: abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
            for k in leaves}


def moved_leaves(ref_grad: Dict[str, float]) -> List[str]:
    """Leaves whose reference gradient is not nought to rounding: at least
    a thousandth of the median leaf's. The others move under Adam by
    round-off alone and are left out of the change."""
    med = statistics.median(ref_grad.values())
    return [k for k, g in ref_grad.items() if g >= 1e-3 * med]


def train_checks(prog: Dict[str, Any], ref: Dict[str, Any],
                 limits: Dict[str, float]) -> Checks:
    checks: Checks = {}
    for i, (lp, lr) in enumerate(zip(prog["losses"], ref["losses"])):
        _check(checks, f"loss_gap.step{i + 1}", abs(lp - lr) / abs(lr),
               limits)
    leaves = sorted(ref["grad_norms"])
    g = norm_gap(prog["grad_norms"], ref["grad_norms"], leaves)
    worst = max(g, key=g.get)
    _check(checks, "grad_norm_gap", g[worst], limits)
    checks["grad_norm_gap"]["leaf"] = worst
    moved = moved_leaves(ref["grad_norms"])
    c = norm_gap(prog["change_norms"], ref["change_norms"], moved)
    worst = max(c, key=c.get)
    _check(checks, "change_norm_gap", c[worst], limits)
    checks["change_norm_gap"]["leaf"] = worst
    return checks


def serve_checks(gaps: List[float], malformed: int,
                 limits: Dict[str, Any]) -> Checks:
    """`gaps`: for every compared served token, how far its float32
    reference logit lies below the reference's best (0 where it is the
    best). A served token tells of the arithmetic only where the reference's
    two best lie close, and a gap is then the margin that the noise
    overcame. Three readings: the widest gap, which swings by its nature;
    the mean gap of the tokens that are not the reference's first, which
    scales with the noise and not with how many near ties the sample holds
    (no such token: 0); and the share of such tokens, which says how many
    the mean rests on."""
    checks: Checks = {}
    n = len(gaps)
    flipped = [g for g in gaps if g > 0]
    _check(checks, "logit_gap", max(gaps) if n else float("nan"), limits)
    _check(checks, "flip_gap_mean",
           (sum(flipped) / len(flipped) if flipped else 0.0) if n
           else float("nan"), limits)
    _check(checks, "token_mismatch_share",
           len(flipped) / n if n else float("nan"), limits)
    checks["logit_gap"]["tokens"] = n
    # an answer that says the wrong thing: not the tokens asked for, or a
    # token outside the vocabulary. Exact, so the limit is 0.
    exact(checks, "malformed_answers", malformed)
    return checks


def all_ok(checks: Checks) -> bool:
    return bool(checks) and all(c["ok"] for c in checks.values())
