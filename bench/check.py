"""The comparison that decides a training cell's ``correct``.

The program's readings are taken by ``bench/run.py`` while set-up drives
the trainer through its first ``check_steps`` steps; the reference's come
from ``bench/reference.py`` following the same steps from the same seed.
Three numbers are compared, each against its limit in the cell's file
(``bench/workloads/<cell>.json``, with the readings it was set from):

- ``loss_gap``: the largest gap, in nats, between a step's loss and the
  reference's, over the checked steps;
- ``grad_gap``: over every leaf and group, the gap between the norm of the
  first gradient as AdamW received it (clipped) and the reference's, over
  the larger of the reference leaf's norm and the median leaf's;
- ``change_gap``: the same for the change of the parameters over the
  checked steps; leaves whose reference gradient is under a thousandth of
  the median leaf's are left out (they move by round-off alone under Adam).
"""

from __future__ import annotations

import numpy as np

ROUNDOFF = 1e-3  # leaves with a gradient under this share of the median's


def reference_readings(cell, seed: int, precision: str = "fp32") -> dict:
    """The reference's readings for ``cell`` at ``seed`` (or the control's,
    with ``precision="fp8"``), following the traffic file's recipe."""
    import jax

    import reference as R
    from run import make_traffic

    with jax.default_matmul_precision("highest"):
        return R.follow(seed, cell.config, cell.traffic["train"],
                        make_traffic(cell, seed),
                        int(cell.check["check_steps"]),
                        precision=precision)


def _norm_gap(got: np.ndarray, want: np.ndarray, mask=None) -> float:
    """Worst leaf's |‖got‖ − ‖want‖| over max(‖want‖, median leaf ‖want‖);
    arrays are (groups, leaves)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    floor = np.median(want, axis=1, keepdims=True)
    gap = np.abs(got - want) / np.maximum(want, floor)
    if mask is not None:
        gap = np.where(mask, gap, 0.0)
    return float(gap.max())


def readings_gaps(prog: dict, ref: dict) -> dict:
    """The three numbers for one pair of readings."""
    g_ref = np.asarray(ref["grad_norms"], np.float64)
    moves = g_ref >= ROUNDOFF * np.median(g_ref, axis=1, keepdims=True)
    return {
        "loss_gap": float(np.max(np.abs(np.asarray(prog["loss"])
                                        - np.asarray(ref["loss"])))),
        "grad_gap": _norm_gap(prog["grad_norms"], g_ref),
        "change_gap": _norm_gap(prog["change_norms"], ref["change_norms"],
                                moves),
    }


def compare(prog: dict, ref: dict, check: dict) -> dict:
    """Each number with its limit from the cell's file."""
    limits = check.get("limits", {})
    return {name: {"value": value,
                   "limit": float(limits.get(name, {}).get("limit", "nan"))}
            for name, value in readings_gaps(prog, ref).items()}
