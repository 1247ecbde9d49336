"""The comparison that decides ``correct``: each number beside its limit."""

from __future__ import annotations

import math
import statistics
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp


def leaf_norms(tree) -> Dict[str, float]:
    """The Euclidean norm of every leaf, by its tree path."""
    sq = jax.jit(lambda t: jax.tree.map(
        lambda a: jnp.sum(a.astype(jnp.float32) ** 2), t))(tree)
    return {jax.tree_util.keystr(p): math.sqrt(float(v))
            for p, v in jax.tree_util.tree_leaves_with_path(sq)}


def leaf_gaps(prog: Dict[str, float], ref: Dict[str, float]
              ) -> Dict[str, float]:
    """Per leaf |‖prog‖ - ‖ref‖| / max(‖ref‖, median ‖ref‖): the gap
    between the norms, not the norm of the difference, against the leaf's
    own size or the median leaf's (some gradients are all but zero)."""
    if sorted(prog) != sorted(ref):
        raise ValueError(f"leaves differ: {sorted(set(prog) ^ set(ref))}")
    floor = statistics.median(ref.values())
    out = {}
    for name, r in ref.items():
        gap = abs(prog[name] - r) / max(r, floor, 1e-30)
        out[name] = gap if math.isfinite(gap) else float("inf")
    return out


def worst_leaf_gap(prog: Dict[str, float], ref: Dict[str, float]
                   ) -> Tuple[float, str]:
    gaps = leaf_gaps(prog, ref)
    where = max(gaps, key=gaps.get)
    return gaps[where], where


class Checks:
    """Numbers compared, each with a limit of its own.  ``correct`` is
    true when every value is finite and within its limit."""

    def __init__(self):
        self.rows: List[Tuple[str, float, float, str]] = []

    def add(self, name: str, value: float, limit: float, note: str = ""):
        self.rows.append((name, float(value), float(limit), note))

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(
            math.isfinite(v) and v <= lim for _, v, lim, _ in self.rows)

    def report(self) -> List[str]:
        out = []
        for name, v, lim, note in self.rows:
            ok = "ok  " if math.isfinite(v) and v <= lim else "FAIL"
            out.append(f"check {ok} {name}: {v:.6g} (limit {lim:.6g})"
                       + (f" [{note}]" if note else ""))
        return out

    def as_dict(self) -> Dict[str, Dict[str, float]]:
        return {n: {"value": v, "limit": lim} for n, v, lim, _ in self.rows}


def training_checks(prog: Dict, ref: Dict, limits: Dict) -> Checks:
    """``prog`` / ``ref``: ``losses`` (a list), ``first_grad_norm`` and
    ``delta_norm`` (per leaf).  Limits: ``loss_rel``, ``grad_norm_gap``
    (worst leaf), ``median_grad_norm_gap`` (the median leaf's gap) and
    ``delta_norm_gap``; ``leaves`` is a regular expression naming the
    leaves the two worst-leaf numbers look at (all of them without it)."""
    import re

    pick = re.compile(limits.get("leaves", ""))

    def picked(d):
        return {k: v for k, v in d.items() if pick.search(k)}

    c = Checks()
    for i, (a, b) in enumerate(zip(prog["losses"], ref["losses"])):
        c.add(f"loss_step{i + 1}_rel", abs(a - b) / abs(b),
              limits["loss_rel"], f"program {a:.6f} reference {b:.6f}")
    gaps = leaf_gaps(prog["first_grad_norm"], ref["first_grad_norm"])
    where = max(picked(gaps), key=gaps.get)
    c.add("first_grad_norm_gap", gaps[where], limits["grad_norm_gap"],
          where)
    c.add("median_leaf_grad_norm_gap", statistics.median(gaps.values()),
          limits["median_grad_norm_gap"], f"{len(gaps)} leaves")
    gaps = picked(leaf_gaps(prog["delta_norm"], ref["delta_norm"]))
    where = max(gaps, key=gaps.get)
    c.add("param_delta_norm_gap", gaps[where], limits["delta_norm_gap"],
          where)
    return c
