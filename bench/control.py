"""The control of the correctness check: the plain reference put in the
program's place, computed in bfloat16, the precision below the float32
the configurations state.

A solve's answer becomes the program's labels with the reference's
objective of them, and the reference's lower bound (the sum of
min(0, cost)), each from costs stored in bfloat16, summed in float32 and
stored in bfloat16. A lower bound in a lower precision stays a bound
only where it rounds down, so ``rounding="down"`` rounds the bound's
terms and its sum toward -inf; ``rounding="nearest"`` rounds them to
nearest, and that bound lands on either side of the float32 one. The
check has to find the control not correct
(``bench/tests/test_chipbench_control.py``; readings on the chip with
``bench/readings.py``).

It runs in numpy on the host: on the TPU, XLA may keep a float32 value
that the program converts to bfloat16 and back (excess precision), so a
device version would not round where it says it does."""
from __future__ import annotations

import math

import ml_dtypes
import numpy as np

from bench.host import HostInstance

BF16 = ml_dtypes.bfloat16


def down(x) -> np.ndarray:
    """float32 -> bfloat16, rounded toward -inf."""
    x = np.asarray(x, np.float32)
    y = x.astype(BF16)
    bits = y.view(np.uint16)
    below = np.where(bits & 0x8000, bits + 1,
                     np.where(bits == 0, 0x8001, bits - 1)).astype(np.uint16)
    return np.where(y.astype(np.float32) > x, below.view(BF16), y)


def nearest(x) -> np.ndarray:
    return np.asarray(x, np.float32).astype(BF16)


def _sum(terms, rnd) -> float:
    return float(rnd(np.float32(terms.astype(np.float32).sum(
        dtype=np.float32))).astype(np.float32))


def objective(inst: HostInstance, labels) -> float:
    u, v, c = inst.valid_edges()
    labels = np.asarray(labels)
    return _sum(nearest(c[labels[u] != labels[v]]), nearest)


def bound(inst: HostInstance, rounding: str = "down") -> float:
    rnd = {"down": down, "nearest": nearest}[rounding]
    _, _, c = inst.valid_edges()
    return _sum(rnd(np.minimum(c, np.float32(0))), rnd)


def control_answer(inst: HostInstance, labels, objective_: float,
                   rounding: str = "down") -> tuple[float, float]:
    """(objective, lower bound) of the control for a solve that returned
    ``labels`` and ``objective_`` (no objective stays none)."""
    obj = objective(inst, labels) if math.isfinite(objective_) \
        else objective_
    return obj, bound(inst, rounding)


def control_solve(program_solve, rounding: str = "down"):
    """``solve(inst)`` whose answer is the control's: the program's
    result with the control's objective and bound in its place."""
    import jax.numpy as jnp

    def solve(inst):
        res = program_solve(inst)
        hi = HostInstance(*(np.asarray(a) for a in inst), planted=None)
        obj, lb = control_answer(hi, np.asarray(res.labels),
                                 float(res.objective), rounding)
        return res._replace(objective=jnp.float32(obj),
                            lower_bound=jnp.float32(lb))
    solve.mode = getattr(program_solve, "mode", "pd")
    return solve
