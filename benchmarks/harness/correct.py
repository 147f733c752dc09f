"""The comparisons that decide ``correct``: the program's numbers against
the plain reference's, each beside a limit of its own (PERF.md gives the
readings every limit was set from)."""

from __future__ import annotations

import statistics

from .common import Check

# A leaf whose first gradient is nought to rounding in the reference (a key
# bias under softmax) moves under Adam by round-off alone: left out of the
# parameters' change by this rule on the reference's gradient, not by name.
DEAD_GRADIENT_SHARE = 1e-3


def norm_gap(prog: dict, ref: dict, leaves=None) -> tuple[float, str]:
    """Worst leaf of |program's norm - reference's norm| over the larger of
    the reference's norm of that leaf and of the median leaf."""
    leaves = sorted(ref) if leaves is None else sorted(leaves)
    if set(leaves) - set(prog):
        missing = sorted(set(leaves) - set(prog))[:3]
        raise KeyError(f"the program has no leaves {missing}")
    med = statistics.median(ref[k] for k in leaves)
    worst, at = -1.0, ""
    for k in leaves:
        gap = abs(prog[k] - ref[k]) / max(ref[k], med)
        if not gap <= worst:  # NaN wins
            worst, at = gap, k
    return float(worst), at


def live_leaves(ref_grad_norms: dict) -> list[str]:
    med = statistics.median(ref_grad_norms.values())
    return [
        k for k, v in ref_grad_norms.items() if v >= DEAD_GRADIENT_SHARE * med
    ]


def training_checks(prog: dict, ref: dict, limits: dict) -> list[Check]:
    """``prog`` and ``ref``: {"losses", "grad_norms", "delta_norms"} of the
    first steps, as ``references.gpt2.train_steps`` returns them."""
    n = len(ref["losses"])
    # A program that logged fewer losses than steps gives no number: 1e30.
    loss_gap = 1e30 if len(prog["losses"]) < n else max(
        abs(float(a) - float(b)) for a, b in zip(prog["losses"], ref["losses"])
    )
    grad_gap, _ = norm_gap(prog["grad_norms"], ref["grad_norms"])
    delta_gap, _ = norm_gap(
        prog["delta_norms"], ref["delta_norms"],
        live_leaves(ref["grad_norms"]),
    )
    values = {
        "loss_gap": loss_gap,
        "grad_norm_gap": grad_gap,
        "delta_norm_gap": delta_gap,
    }
    return [Check(k, v, float(limits[k])) for k, v in values.items()]


def serving_checks(logit_gap: float, limits: dict) -> list[Check]:
    return [Check("logit_gap", float(logit_gap), float(limits["logit_gap"]))]
