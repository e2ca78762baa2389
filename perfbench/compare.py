"""The comparison that decides ``correct``: the program's parameters after
its first rounds against the reference's after the same rounds, leaf by
leaf.

Numbers (each has a limit of its own in ``limits/<workload>.json``):

  update_gap   round 1's update (the server's "gradient" as its update rule
               gets it): worst leaf's |‖Δ_sys‖ − ‖Δ_ref‖| over the larger of
               ‖Δ_ref‖ of that leaf and of the median leaf;
  change_gap   the same for the change after the last compared round;
  update_diff  ‖Δ_sys − Δ_ref‖ / ‖Δ_ref‖ of round 1 over all compared leaves:
               both rounds start from the same weights, so this catches an
               update that has the right size and the wrong direction;
  update_total_gap, change_total_gap
               |‖Δ_sys‖ − ‖Δ_ref‖| / ‖Δ_ref‖ over all compared leaves at
               once, for updates so sparse (top-k) that single leaves hold a
               handful of moved weights.

Leaves whose round-1 reference update is under a thousandth of the median
leaf's are left out of every number: they move by round-off alone.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

EXCLUDE_BELOW = 1e-3


@jax.jit
def _sq_norms(p0, s1, r1, sn, rn):
    f = lambda a: a.astype(jnp.float32)
    sq = lambda a: jnp.sum(jnp.square(a))
    return jax.tree.map(
        lambda a, b, c, d, e: jnp.stack([
            sq(f(b) - f(a)), sq(f(c) - f(a)), sq(f(b) - f(c)),
            sq(f(d) - f(a)), sq(f(e) - f(a))]), p0, s1, r1, sn, rn)


def leaf_names(tree) -> list:
    return [jax.tree_util.keystr(k) for k, _ in
            jax.tree_util.tree_flatten_with_path(tree)[0]]


def numbers(p0, sys1, ref1, sysn, refn) -> dict:
    """The compared numbers from five parameter trees of one layout (any
    mix of device and host arrays)."""
    names = leaf_names(p0)
    stats = np.asarray(jax.device_get(jnp.stack(
        jax.tree.leaves(_sq_norms(p0, sys1, ref1, sysn, refn)))),
        np.float64)
    n = np.sqrt(stats)          # per leaf: |s1-p0| |r1-p0| |s1-r1| |sn-p0| |rn-p0|
    med = float(np.median(n[:, 1]))
    keep = n[:, 1] >= EXCLUDE_BELOW * med
    med_n = float(np.median(n[keep, 4])) if keep.any() else 0.0

    def worst(sys_col, ref_col, m):
        gaps = np.abs(n[:, sys_col] - n[:, ref_col]) \
            / np.maximum(np.maximum(n[:, ref_col], m), 1e-30)
        gaps = np.where(keep, gaps, -1.0)
        i = int(np.argmax(gaps))
        return float(gaps[i]), names[i]

    ug, ug_leaf = worst(0, 1, med)
    cg, cg_leaf = worst(3, 4, med_n)
    def total_gap(sys_col, ref_col):
        a = math.sqrt(float(np.sum(stats[keep, sys_col])))
        b = math.sqrt(float(np.sum(stats[keep, ref_col])))
        return abs(a - b) / max(b, 1e-30)

    ref_norm = math.sqrt(float(np.sum(stats[keep, 1])))
    diff = math.sqrt(float(np.sum(stats[keep, 2]))) / max(ref_norm, 1e-30)
    utg, ctg = total_gap(0, 1), total_gap(3, 4)
    if not np.all(np.isfinite(n)):
        ug = cg = diff = utg = ctg = float("inf")
    return {"update_gap": ug, "change_gap": cg, "update_diff": diff,
            "update_total_gap": utg, "change_total_gap": ctg,
            "worst_update_leaf": ug_leaf, "worst_change_leaf": cg_leaf,
            "excluded_leaves": [nm for nm, k in zip(names, keep) if not k],
            "ref_update_norm": ref_norm}


def verdict(nums: dict, limits: dict) -> tuple:
    """(correct, [{name, value, limit}]) for every limited number; a number
    that is missing or not finite is not correct."""
    rows, ok = [], True
    for name, limit in limits.items():
        v = nums.get(name)
        good = v is not None and math.isfinite(v) and v <= limit
        ok &= good
        rows.append({"name": name, "value": v, "limit": limit})
    return ok, rows
