"""The names the program gives its work in a profiler trace.

Device scopes (``jax.named_scope``) are compile-time HLO metadata: each
instruction of the compiled step carries its scope path in
``metadata={op_name=...}``, and a profile's device events carry it too.
They cost nothing at run time. One scope marks each layer boundary of the
training step, in the one place that boundary is crossed:

    grad      train/loop.py   the grad pipeline's value_and_grad
    model     train/grad.py   the per-worker loss (and sharded_loss)
    lookup    kernels/pack.py take_rows reading a narrow packed leaf's
              rows straight from the buffer, inside model
    unpack    kernels/pack.py the packed buffer's per-leaf views
    pack      kernels/pack.py a pytree flattened into a packed buffer
    opt       train/loop.py   the optimizer step
    adam      core/dadam.py   the local Adam half-step
    gossip    core/dadam.py, core/cdadam.py   the communication round
    compress  core/cdadam.py, kernels/ops.py   CD-Adam's payload
              encoding inside gossip
    damping   train/loop.py   the batch-damping state update

Autodiff keeps the forward scope in the backward pass, wrapped in the
transformation: forward ops read ``.../grad/jvp(model)/...``, their
gradient ``.../grad/transpose(jvp(model))/...``.

Host spans (``jax.profiler.TraceAnnotation``) land in the same trace as
the device events, on the same clock. ``DecentralizedTrainer.fit`` marks
each iteration with ``StepTraceAnnotation("train", step_num=<global
step>)`` and, inside it, ``fit.batch`` (next batch, placed),
``fit.watch`` (the recompile watch, when armed), ``fit.dispatch`` (the
jitted step call), ``fit.hook`` (the publish hook) and ``fit.log`` (the
log point: the loss sync, the consensus error, the log appends and the lr
decay check). With no profiler running each span costs about a
microsecond.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax


def scoped(name: str) -> Callable[[Callable], Callable]:
    """Decorator: trace the function's body inside ``jax.named_scope(name)``.
    The scope is entered afresh on every call, so the decorated function
    may be traced from several threads at once."""
    def wrap(fn: Callable) -> Callable:
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return inner
    return wrap
