"""The system under test, built from a cell's configuration and traffic
files: the program's model loss, its optimizer and its
``DecentralizedTrainer``, as a user of the program builds them. Also the
configuration's plain reference loss and its model FLOPs per step.

What belongs to one model family (the program's loss, the seeded weights,
the reference, the FLOPs) comes from the configuration's family file,
``families/<family>.py``; this module builds the optimizer and the
trainer around it and names no family.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable, Optional

import jax

from chipbench import spec


@dataclasses.dataclass
class System:
    trainer: Any
    make_params: Callable[[jax.Array], Any]
    ref_loss: Callable[..., jax.Array]
    precision: Optional[str]
    flops_per_step: float        # model FLOPs of one step, all workers

    def context(self):
        """The matmul precision the configuration states, around every
        trace of the program's step."""
        if self.precision is None:
            return contextlib.nullcontext()
        return jax.default_matmul_precision(self.precision)


def build(config: dict, traffic: dict, *, mesh: Any = None,
          loss_wrap: Optional[Callable] = None,
          step_wrap: Optional[Callable] = None) -> System:
    """``loss_wrap(loss) -> loss`` and ``step_wrap(opt.step) -> step``
    plant a fault in the program's model loss or optimizer step (the
    harness's own tests use them)."""
    from repro.core import make_optimizer
    from repro.train import DecentralizedTrainer

    o = traffic["optimizer"]
    K = o["workers"]
    fam = spec.family(config["family"])
    loss = fam.program_loss(config)
    if loss_wrap is not None:
        loss = loss_wrap(loss)
    opt_kw = {k: o[k] for k in ("eta", "beta1", "beta2", "tau", "period")}
    if o["name"] == "cd-adam":
        opt_kw.update(gamma=o["gamma"], compressor=o["compressor"])
    opt = make_optimizer(o["name"], K=K, topology=o["topology"],
                         backend=o["backend"], comm=o["comm"], mesh=mesh,
                         **opt_kw)
    if step_wrap is not None:
        opt = dataclasses.replace(opt, step=step_wrap(opt.step))
    trainer = DecentralizedTrainer(lambda p, b: loss(p, b), opt,
                                   donate=True)
    batch = traffic["batch_per_worker"]
    return System(trainer, fam.make_params(config), fam.ref_loss(config),
                  config.get("matmul_precision"),
                  fam.flops_per_example(config, traffic) * batch * K)
