"""How ``correct`` is decided for a training cell.

Set-up drives the program's compiled step, through ``fit`` and the
window's own feed, for its first steps (``steps_checked``: through the
first gossip round whose exchange shows in the parameters), on batches
that all differ. It reads, from the program's own outputs:

* each step's loss (``fit``'s log, the mean over the workers);
* the first gradient as the optimizer got it: Adam's first moment after
  step 1 is ``(1 - beta1) g``, so ``g = m / (1 - beta1)``;
* the change of every parameter after the last of those steps.

After the window the plain reference follows the same steps from the
same seed. Three numbers are compared, each against its limit:

``loss_gap``
    the largest relative gap of a step's loss;
``grad_norm_gap``
    over every (worker, leaf), the gap between the program's and the
    reference's norm of the first gradient, over the larger of that
    leaf's reference norm and the median leaf's;
``change_norm_gap``
    the same for the norm of the parameter change, leaving out leaves
    whose reference gradient is under 1e-3 of the median leaf's (their
    change is Adam on round-off alone).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

NAMES = ("loss_gap", "grad_norm_gap", "change_norm_gap")
ROUNDOFF_LEAF = 1e-3


@dataclasses.dataclass
class Readings:
    losses: List[float]          # per step, mean over workers
    grad: np.ndarray             # (leaves, K) first-gradient norms
    change: np.ndarray           # (leaves, K) parameter-change norms


@jax.jit
def _norms(stacked):
    """Per (leaf, worker) L2 norms of a K-stacked tree, shape (leaves, K)."""
    def one(x):
        x = x.astype(jnp.float32)
        return jnp.sqrt(jnp.sum(x * x, axis=tuple(range(1, x.ndim))))
    return jnp.stack([one(x) for x in jax.tree_util.tree_leaves(stacked)])


@jax.jit
def _change_norms(stacked, p0):
    diff = jax.tree_util.tree_map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32)[None],
        stacked, p0)
    return _norms(diff)


def stacked_norms(stacked) -> np.ndarray:
    return np.asarray(_norms(stacked), np.float64)


def change_norms(stacked, p0) -> np.ndarray:
    return np.asarray(_change_norms(stacked, p0), np.float64)


def _gap(prog: np.ndarray, ref: np.ndarray, keep: np.ndarray) -> float:
    med = float(np.median(ref[keep]))
    den = np.maximum(ref, med)
    gap = np.abs(prog - ref) / np.where(den > 0, den, 1.0)
    return float(np.max(np.where(keep, gap, 0.0)))


def compare(prog: Readings, ref: Readings) -> Dict[str, float]:
    """The three compared numbers; NaN or inf in the program's readings
    gives inf."""
    if not (np.all(np.isfinite(prog.losses)) and
            np.all(np.isfinite(prog.grad)) and
            np.all(np.isfinite(prog.change))):
        return {n: float("inf") for n in NAMES}
    lp, lr = np.asarray(prog.losses), np.asarray(ref.losses)
    every = np.ones(ref.grad.shape, bool)
    moved = ref.grad >= ROUNDOFF_LEAF * np.median(ref.grad)
    return {
        "loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
        "grad_norm_gap": _gap(prog.grad, ref.grad, every),
        "change_norm_gap": _gap(prog.change, ref.change, moved),
    }


def judge(values: Dict[str, float], limits: Optional[dict]
          ) -> tuple[bool, Dict[str, dict]]:
    """(correct, {name: {value, limit}}). No limits file: not correct. A
    number the file gives no limit (no reading separated it from the
    program's) is printed and not judged."""
    out = {}
    ok = limits is not None
    for n in NAMES:
        lim = None if limits is None else limits["limits"].get(n)
        v = values[n]
        out[n] = {"value": v, "limit": lim}
        if limits is not None and lim is not None:
            ok = ok and bool(np.isfinite(v)) and v <= lim
    return ok, out


def steps_checked(traffic: dict) -> int:
    """Steps the check follows: through the first gossip round for D-Adam
    (``period``); through the second for CD-Adam, whose first round mixes
    the all-zero estimates and whose exchange shows in the parameters only
    at the next round (``2 * period``)."""
    o = traffic["optimizer"]
    return o["period"] * (2 if o["name"] == "cd-adam" else 1)


def control_mode(config: dict) -> str:
    """The precision one step below the one the configuration states:
    ``high`` (three bf16 passes) under float32 at ``highest``, ``fp8``
    under bfloat16 compute, else ``bf16``."""
    if config.get("matmul_precision") == "highest":
        return "high"
    if config.get("compute_dtype") == "bfloat16":
        return "fp8"
    return "bf16"


def drive_first_steps(trainer, state, fit, steps: int, beta1: float,
                      p0) -> tuple:
    """The program's first ``steps`` steps, one ``fit`` call each (each
    logs its loss); returns (state, log, Readings). ``p0`` is the params
    the state was made from."""
    log, losses, grad = None, [], None
    for t in range(steps):
        state, log = fit(state, 1, log)
        losses.append(log.loss[-1])
        if t == 0:
            grad = stacked_norms(state.moments.m) / (1.0 - beta1)
    change = change_norms(trainer.opt.params_of(state), p0)
    return state, log, Readings(losses, grad, change)


def _half(x: jax.Array) -> jax.Array:
    """Half of a worker's batch: its first half of the examples, or with
    one example (a sequence) the first half of its tokens."""
    if x.shape[0] > 1:
        return x[: x.shape[0] // 2]
    return x[:, : x.shape[1] // 2 + 1]


def reference_readings(system, config: dict, traffic: dict, key_w, batches:
                       Sequence[dict], *, mode: str = "highest",
                       fault: Optional[str] = None) -> Readings:
    """The plain reference over ``batches`` (K-stacked, one per step).

    ``mode`` below ``highest`` is the control; ``fault`` plants one in
    the reference put in the program's place: ``'half'`` (half of each
    worker's batch left out, the mean over the rest) or ``'no_gossip'``
    (the exchange left out)."""
    from reference import cdadam, dadam

    o = traffic["optimizer"]
    hp = {k: o[k] for k in ("eta", "beta1", "beta2", "tau", "period")}
    K = o["workers"]

    @jax.jit
    def grad_fn(p, b):
        if fault == "half":
            b = jax.tree_util.tree_map(_half, b)
        return jax.value_and_grad(lambda q: system.ref_loss(q, b, mode=mode)
                                  )(p)

    p0 = system.make_params(key_w)
    per_step = [[jax.tree_util.tree_map(lambda x, k=k: x[k], b)
                 for k in range(K)] for b in batches]
    if o["name"] == "d-adam":
        losses, first, xs = dadam.run(p0, per_step, grad_fn, hp,
                                      gossip=fault != "no_gossip")
    elif o["name"] == "cd-adam" and o["compressor"] == "sign":
        losses, first, xs = cdadam.run(p0, per_step, grad_fn, hp,
                                       gamma=o["gamma"],
                                       exchange=fault != "no_gossip")
    else:
        raise ValueError(f"no reference for optimizer {o['name']!r}")
    stack = lambda ts: jax.tree_util.tree_map(      # noqa: E731
        lambda *a: jnp.stack(a), *ts)
    grad = stacked_norms(stack(first))
    del first
    change = change_norms(stack(xs), p0)
    return Readings(losses, grad, change)
