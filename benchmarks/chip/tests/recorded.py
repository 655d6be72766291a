"""The readers' context over a trace recorded on the chip (``data/``):
three steps of the DeepFM cell on a TPU v5 lite, cut from a ``--trace 1``
run (the ``XLA Ops`` events by instruction and opcode, the host spans,
the kernel calls of the compiled step; in the scoped recording also the
step program's runs and each of its instructions' opcode and scope
path)."""
from __future__ import annotations

import json
import pathlib

from chipbench import layers, marks, spec, trace

DATA = pathlib.Path(__file__).parent / "data"
FLOPS_PER_STEP = 1.1748e10        # deepfm flops_per_example x 4096


def load(name: str) -> dict:
    return json.loads((DATA / name).read_text())


def context(fix: dict, scoped: bool = False) -> layers.Context:
    """With ``scoped``, the context holds the recording's step program;
    without, it has none (a program without scopes)."""
    ops = sorted((trace.Op(n, s, e, k) for n, k, s, e in fix["ops"]),
                 key=lambda o: (o.start, -o.end))
    tr = trace.Trace([trace.Device(0, ops)], tuple(fix["window"]),
                     [trace.Op(n, s, e) for n, s, e in fix["host"]])
    calls = {k: trace.KernelCall(
        k, c["kernel"], tuple(trace.Array(d, tuple(s)) for d, s in
                              c["operands"]),
        tuple(trace.Array(d, tuple(s)) for d, s in c["results"]),
        c["arity"]) for k, c in fix["calls"].items()}
    ctx = layers.Context(tr, fix["steps"], spec.peaks_for("TPU v5 lite"),
                         FLOPS_PER_STEP, calls, layers.kernel_modules())
    ctx.step_program = marks.StepProgram(
        {n: tuple(v) for n, v in fix["scopes"].items()},
        {0: [tuple(r) for r in fix["runs"]]}) if scoped else None
    return ctx
