"""The comparison that decides ``correct`` for a training cell.

Four kinds of number, each a gap between what the timed path produced and
what the plain reference gives for the same steps:

- ``loss_gap``: the largest, over the compared steps, of
  ``|loss_program - loss_reference| / |loss_reference|``;
- ``grad_gap``: by the worst leaf, the gap between the norm of the first
  gradient as the program's optimizer got it (worked out from its state
  after one step) and the reference's, against the reference's norm of that
  leaf or of the median leaf, whichever is larger;
- ``moment_gap``: where the timed call is a whole epoch in one dispatch, so
  that the first gradient is not to be had, the same measure on Adam's first
  moment after that call;
- ``change_gap``: the same measure on the parameters' change over the
  compared steps.

Leaves whose reference gradient is under a thousandth of the median leaf's
are left out of all three per-leaf numbers. Such a leaf is a bias that feeds
a batch norm: its true gradient is nought, the reference's is round-off, and
the program's in bfloat16 is the residue of a sum of B*H*W cotangents that
cancel only in exact arithmetic (read at 8 x the median leaf's norm on the
stem bias of ResNet-50), which Adam then moves by round-off alone.

It is the gap between two norms, not the norm of a difference: a leaf that
reads about 1 has not moved, or has moved double, on one side. Beside each
per-leaf number stand the median leaf's gap (``*_gap_median``) and the
direction gap (``*_direction_gap``, 1 - cosine over all kept leaves as one
vector); which of them a cell is held to is in its limits file.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np


def leaf_norms(leaves: Sequence) -> np.ndarray:
    return np.array([float(np.linalg.norm(np.asarray(a, np.float64).ravel()))
                     for a in leaves])


def leaf_gaps(program: np.ndarray, reference: np.ndarray) -> np.ndarray:
    program, reference = np.asarray(program), np.asarray(reference)
    denom = np.maximum(reference, np.median(reference))
    return np.abs(program - reference) / np.maximum(denom, 1e-300)


def worst_gap(program: np.ndarray, reference: np.ndarray,
              keep: Optional[np.ndarray] = None) -> Tuple[float, int]:
    """(largest gap, its leaf index) of two vectors of per-leaf norms."""
    gaps = leaf_gaps(program, reference)
    if keep is not None:
        gaps = np.where(keep, gaps, 0.0)
    i = int(np.argmax(gaps))
    return float(gaps[i]), i


def median_gap(program: np.ndarray, reference: np.ndarray,
               keep: Optional[np.ndarray] = None) -> float:
    """The median leaf's gap: steady from seed to seed where the worst
    leaf's is the noise of one small leaf."""
    gaps = leaf_gaps(program, reference)
    return float(np.median(gaps[keep] if keep is not None else gaps))


def moving_leaves(reference_grad_norms: np.ndarray) -> np.ndarray:
    """Leaves whose reference gradient is at least a thousandth of the
    median leaf's."""
    g = np.asarray(reference_grad_norms)
    return g >= 1e-3 * np.median(g)


def loss_gap(program: Sequence[float], reference: Sequence[float]) -> float:
    p, r = np.asarray(program, np.float64), np.asarray(reference, np.float64)
    if p.shape != r.shape or not np.all(np.isfinite(p)):
        return float("inf")
    return float(np.max(np.abs(p - r) / np.abs(r)))


def direction_gap(program: Sequence, reference: Sequence,
                  keep: Optional[np.ndarray] = None) -> float:
    """1 - cosine between the program's and the reference's leaves taken as
    one vector (kept leaves only). The norms above average rounding away (a
    batch-mean gradient of this network is 30% off its float32 value element
    by element in bfloat16 and still within a tenth by its norm); the
    direction does not, so this is the number that tells a precision from
    the next lower one."""
    dot = pp = rr = 0.0
    for i, (a, b) in enumerate(zip(program, reference)):
        if keep is not None and not keep[i]:
            continue
        a = np.asarray(a, np.float64).ravel()
        b = np.asarray(b, np.float64).ravel()
        dot += float(a @ b)
        pp += float(a @ a)
        rr += float(b @ b)
    if pp == 0.0 or rr == 0.0:
        return 1.0
    return 1.0 - dot / np.sqrt(pp * rr)


def training_gaps(program: Dict, reference: Dict) -> Dict[str, float]:
    """``program`` / ``reference``: ``losses`` (list) and lists of leaves in
    the same order: ``grad`` (the first gradient), or ``moment`` (Adam's
    first moment after the timed call, where that call is a whole epoch),
    and ``change`` (of the parameters over the compared steps)."""
    out = {"loss_gap": loss_gap(program["losses"], reference["losses"])}
    keep = None
    for what in ("grad", "moment"):
        if what not in reference:
            continue
        ref_norms = leaf_norms(reference[what])
        prog_norms = leaf_norms(program[what])
        keep = moving_leaves(ref_norms)
        out[what + "_gap"], _ = worst_gap(prog_norms, ref_norms, keep)
        out[what + "_gap_median"] = median_gap(prog_norms, ref_norms, keep)
        out[what + "_direction_gap"] = direction_gap(program[what], reference[what], keep)
    if "change" in reference:
        ref_norms = leaf_norms(reference["change"])
        prog_norms = leaf_norms(program["change"])
        out["change_gap"], worst = worst_gap(prog_norms, ref_norms, keep)
        out["change_gap_median"] = median_gap(prog_norms, ref_norms, keep)
        out["change_direction_gap"] = direction_gap(program["change"],
                                                    reference["change"], keep)
        out["change_gap_leaf"] = float(worst)
    return out


def judge(gaps: Dict[str, float], limits: Dict[str, float]
          ) -> Tuple[bool, List[Tuple[str, float, float]]]:
    """Every limit of the cell has to be met by a number that was read: a
    number that is missing or not finite fails."""
    rows, ok = [], True
    for name, limit in limits.items():
        value = gaps.get(name, float("nan"))
        rows.append((name, value, limit))
        if not (np.isfinite(value) and value <= limit):
            ok = False
    return ok, rows
