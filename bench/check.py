"""The comparison that decides ``correct``.

What is compared, from the first three rounds the timed path runs (set-up
drives the same trainer, feed and compiled round that the window then
uses):

- ``loss``: every client's loss at every local step of the three rounds,
  as the worst relative gap to the reference's;
- ``step1_change``: per leaf, the norm of the first server step (the
  parameters after round 1 minus the seed's weights: the mean window
  delta the server applies);
- ``step3_change``: per leaf, the norm of the parameters' change after
  three rounds.

A leaf's gap is ``|program norm - reference norm|`` over the larger of the
reference's norm of that leaf and of the median leaf; the worst leaf
counts.  Leaves the reference does not move (first-step norm under a
thousandth of the median leaf's) are left out.  Each number has its limit
in ``bench/limits/<cell>.json``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, List, Optional

import numpy as np

CHECKED_ROUNDS = 3
NUMBERS = ("loss", "step1_change", "step3_change")
TINY = 1e-3


@dataclass
class Readings:
    losses: np.ndarray             # [rounds, K, C]
    step1: Dict[str, float]        # leaf -> norm of the first server step
    step3: Dict[str, float]        # leaf -> norm of the change after 3


def moved_leaves(ref: Readings) -> List[str]:
    med = float(np.median(list(ref.step1.values())))
    return sorted(k for k, v in ref.step1.items() if v >= TINY * med)


def leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
             leaves: List[str]) -> float:
    med = float(np.median([ref[k] for k in leaves]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in leaves)


def compare(prog: Readings, ref: Readings) -> Dict[str, float]:
    leaves = moved_leaves(ref)
    loss = float(np.max(np.abs(prog.losses - ref.losses)
                        / np.abs(ref.losses)))
    return {"loss": loss,
            "step1_change": leaf_gap(prog.step1, ref.step1, leaves),
            "step3_change": leaf_gap(prog.step3, ref.step3, leaves)}


def judge(numbers: Dict[str, float], limits: Dict[str, float]):
    """``(correct, {name: {"value", "limit"}})``.  A number that is not
    finite fails."""
    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in NUMBERS}
    ok = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks


def reference_readings(ref, init, norms, s32, batches,
                       fault: Optional[str] = None) -> Readings:
    """Readings of the plain reference ``ref`` (a
    ``bench.reference.round.Reference``) over the checked rounds, from the
    seed's weights ``init(s32)``.

    ``fault`` plants one of the faults the comparison has to catch:
    ``half_batch`` (the second half of each step's rows replaced by the
    first half, so the mean runs over half the batch), ``no_exchange``
    (only client 0's delta reaches the server)."""
    import jax.numpy as jnp
    cast = ref.dtype != jnp.float32
    p = ref.cast(init(s32))
    p0 = p if cast else None       # float32 norms regenerate it from s32
    losses, step1 = [], None
    for r, tokens in enumerate(batches):
        if fault == "half_batch":
            tokens = halve(tokens)
        p, lc = ref.round(p, tokens, r,
                          clients=(0,) if fault == "no_exchange" else None)
        losses.append(np.asarray(lc, np.float64))
        if r == 0:
            step1 = _norms_from(norms, p, p0, s32, cast)
    step3 = _norms_from(norms, p, p0, s32, cast)
    return Readings(np.stack(losses), step1, step3)


def _norms_from(norms, p, p0, s32, cast):
    """Leaf norms of ``p - p0``: through the seed (``norms``) in float32,
    or directly against the cast start weights in another type."""
    import jax
    if not cast:
        return {k: float(v) for k, v in jax.device_get(norms(p, s32)).items()}
    from bench.weights import leaves, path_name
    import jax.numpy as jnp
    return {path_name(k): float(jnp.sqrt(jnp.sum(jnp.square(
                a.astype(jnp.float32) - b.astype(jnp.float32)))))
            for (k, a), (_, b) in zip(leaves(p), leaves(p0))}


def halve(tokens: np.ndarray) -> np.ndarray:
    """``[K, C, B, S]`` tokens with the second half of each step's rows
    (clients x sequences) copied from the first half."""
    K, C, B, S = tokens.shape
    rows = tokens.reshape(K, C * B, S).copy()
    h = (C * B) // 2
    rows[:, C * B - h:] = rows[:, :h]
    return rows.reshape(K, C, B, S)
