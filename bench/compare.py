"""The numbers ``correct`` compares, each beside its limit.

Program and reference readings of the first steps (``Readings``; the
cell's limit file gives their number, ``check_steps``) give the numbers
below. The reference follows the program's upload decisions
(``refstep.reference_run(follow=...)``), so that one decision taken the
other way at the gate's edge does not send the two down different paths;
each decision is judged by the reference's own LHS and RHS instead, as a
served token is judged by the reference's logits:

* ``loss_gap`` — the largest |loss_program − loss_reference| over the
  first three steps (nats): later, the loss spike that Adam's first steps
  set off amplifies any difference, and the numbers of the gate take over;
* ``grad_gap`` — of the first aggregate ∇̄^0 as the optimizer gets it, the
  worst leaf's |‖g_p‖ − ‖g_r‖| over the larger of the reference leaf's
  norm and the median leaf's;
* ``update_gap`` — the same of θ^3 − θ^0, over the leaves whose reference
  gradient is at least a thousandth of the median leaf's (the others move
  by round-off alone);
* ``lhs_gap`` (rules with a LHS) — the largest relative gap of the rule's
  per-worker LHS at steps 2 and 3 (at step 1 it is 0 by construction).
  Later, where a worker's gradient turns sharply between two iterates, its
  LHS spikes far above the RHS and bfloat16 and float32 part there by tens
  of percent with no bearing on the decision; what the LHS decides there,
  read after a skip at the iterate τ_m >= 2 steps back from the
  stale-iterate ring, ``gate_gap`` judges;
* ``rhs_gap`` (rules with a LHS) — the largest relative gap of the rule's
  RHS over the steps after the first (0 at the first, by construction);
* ``gate_gap`` (rules with a LHS) — of the program's decisions that the
  reference's own gate takes the other way, the widest |ln(LHS / RHS)| of
  the reference (0 when none): a sound gate errs only where LHS and RHS
  lie within their own gaps of each other; one that never skips, or reads
  a wrong RHS, errs far from the edge;
* ``forced_skips`` (rules with a LHS) — skips where the reference's τ_m
  has reached max_delay, so that the worker has to upload (an exact
  count);
* ``gate_audit`` and ``rhs_audit`` (rules with a LHS) — every decision of
  the checked steps and of the measured window, against the rule written
  out here and applied to the program's own readings: the count of
  decisions that ``upload = LHS > RHS or τ_m >= max_delay`` takes the
  other way, and the largest relative gap of the program's RHS from
  (c / d_max) · Σ of its last d_max reported ‖θ^{k+1} − θ^k‖². They reach
  the window's skips, which come long after the checked steps;
* ``window_nonfinite`` — window steps whose loss is not finite (limit 0).

A limit file gives each compared number its limit; a number it leaves out
is printed and not compared.
"""
from __future__ import annotations

import numpy as np

ORDER = ("loss_gap", "grad_gap", "update_gap", "lhs_gap", "rhs_gap",
         "gate_gap", "forced_skips", "gate_audit", "rhs_audit",
         "window_nonfinite")
TINY_GRAD = 1e-3
LOSS_STEPS = LHS_STEPS = 3


def worst_leaf_gap(prog: dict, ref: dict, leaves=None) -> float:
    leaves = sorted(ref) if leaves is None else leaves
    med = float(np.median([ref[k] for k in ref]))
    return max(abs(float(prog[k]) - float(ref[k]))
               / max(float(ref[k]), med, 1e-30) for k in leaves)


def moving_leaves(ref_grad_norms: dict) -> list:
    med = float(np.median(list(ref_grad_norms.values())))
    return sorted(k for k, g in ref_grad_norms.items()
                  if g >= TINY_GRAD * med)


def numbers(prog, ref) -> dict:
    """Every compared number of program readings against reference ones."""
    out = {
        "loss_gap": max(abs(a - b) for a, b in zip(prog.losses[:LOSS_STEPS],
                                                   ref.losses[:LOSS_STEPS])),
        "grad_gap": worst_leaf_gap(prog.grad_norms, ref.grad_norms),
        "update_gap": worst_leaf_gap(prog.update_norms, ref.update_norms,
                                     moving_leaves(ref.grad_norms)),
    }
    if ref.lhs is not None and prog.lhs is not None:
        gaps = [abs(p - r) / max(abs(r), 1e-30)
                for ps, rs in zip(prog.lhs[1:LHS_STEPS],
                                  ref.lhs[1:LHS_STEPS])
                for p, r in zip(ps, rs)]
        out["lhs_gap"] = max(gaps) if gaps else 0.0
        out["rhs_gap"] = max((abs(p - r) / max(abs(r), 1e-30)
                              for p, r in zip(prog.rhs[1:], ref.rhs[1:])),
                             default=0.0)
        out.update(gate_numbers(prog.masks, ref))
    return out


def gate_numbers(masks: list, ref) -> dict:
    """``gate_gap`` and ``forced_skips`` of the decisions ``masks`` against
    the reference readings that followed them."""
    gap, forced_skips = 0.0, 0
    for mask, lhs, rhs, forced in zip(masks, ref.lhs, ref.rhs, ref.forced):
        for up, l_w, f_w in zip(mask, lhs, forced):
            if f_w:
                forced_skips += not up
            elif bool(up) != (l_w > rhs):
                gap = max(gap, abs(float(np.log(max(l_w, 1e-30)
                                                / max(rhs, 1e-30)))))
    return {"gate_gap": gap, "forced_skips": float(forced_skips)}


def audit(rule: dict, masks, lhs, rhs, dtheta_sq) -> dict:
    """``gate_audit`` and ``rhs_audit`` of a run's every step from its start
    (per step: upload mask, per-worker LHS and RHS as float32, and
    ‖θ^{k+1} − θ^k‖²)."""
    c, d_max, max_delay = rule["c"], int(rule["d_max"]), int(rule["max_delay"])
    masks, lhs = np.asarray(masks, bool), np.asarray(lhs, np.float32)
    rhs = np.asarray(rhs, np.float32)
    dsq = np.asarray(dtheta_sq, np.float64)
    tau = np.full(masks.shape[1], max_delay)
    wrong, worst = 0, 0.0
    for k in range(masks.shape[0]):
        want = c / d_max * float(dsq[max(0, k - d_max):k].sum())
        worst = max(worst, abs(float(rhs[k]) - want) / max(want, 1e-30))
        decide = (lhs[k] > rhs[k]) | (tau >= max_delay)
        wrong += int(np.sum(decide != masks[k]))
        tau = np.where(masks[k], 1, tau + 1)
    return {"gate_audit": float(wrong), "rhs_audit": worst}


def verdict(nums: dict, limits: dict) -> tuple:
    """(correct, [(name, value, limit or None)]) — correct when every
    number that has a limit is at or under it and none is NaN, and at
    least one number is compared."""
    rows, ok, compared = [], True, 0
    lim = limits.get("limits", {})
    for name in ORDER:
        if name not in nums:
            continue
        value, bound = nums[name], lim.get(name)
        rows.append((name, value, bound))
        if bound is not None:
            compared += 1
            ok &= bool(np.isfinite(value)) and value <= bound
    return ok and compared > 0, rows
