"""The program's own marks in a trace: the named scopes of its compiled
step and the host spans of its training loop.

The program traces each layer of the step under a ``jax.named_scope``
(``grad``, ``model``, ``unpack``, ``opt``, ``adam``, ``gossip``, ...),
which the compiler keeps as each instruction's ``metadata={op_name=...}``.
The ``XLA Modules`` line of a TPU plane holds each run of each program.
``step_program(ctx)`` takes the program that ran longest in the traced
window as the step, finds its compiled module among the process's live
executables (the one that defines every instruction the step's runs
executed, with the same opcode), and maps each instruction to its scope
path with ``op_scopes``. An op counts as the step's when it starts inside
one of the step's runs on its chip and its instruction and opcode are the
step's, so the log point's eager programs, whose instruction names may
collide with the step's, stay out. Where the program has no scopes, the
readers find no op under the scope they read and report nothing.
"""
from __future__ import annotations

import bisect
import dataclasses
import pathlib
import re
from typing import Callable, Dict, Iterable, List, Optional, Set, Tuple

from chipbench import spec, trace

TRACE_ROOT = spec.ROOT / ".bench_trace"     # where run.py records
MODULES_LINE = "XLA Modules"
_MODULE = re.compile(r"^(.*)\((\d+)\)$")
_COMP = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*?)\s([\w\-]+)\((.*)$")
_REF = re.compile(r"%([\w.\-]+)")
_OP_NAME = re.compile(r'metadata=\{[^}]*?\bop_name="([^"]*)"')
_WRAPPER = re.compile(r"(\w+)\(")
_CALL = re.compile(r"p?jit\(")
# the packed buffer's views: a layer of their own, not the model's
PACKED_VIEW = {"unpack", "pack"}

Runs = Dict[int, List[Tuple[float, float]]]      # chip id -> [(start, end) ns]


# ------------------------------ HLO scopes ----------------------------------

def _split(rest: str) -> Tuple[str, str]:
    """(operand list, attributes) of an op; ``rest`` follows ``opcode(``."""
    depth = 1
    for i, ch in enumerate(rest):
        depth += (ch == "(") - (ch == ")")
        if depth == 0:
            return rest[:i], rest[i + 1:]
    return rest, ""


def hlo_ops(hlo_text: str
            ) -> Dict[str, Tuple[str, str, str, List[str], List[str]]]:
    """``{instruction: (computation, opcode, op_name, operands, refs of
    its attributes)}`` of a module's HLO text; op_name ``""`` where the
    instruction has none."""
    out, comp = {}, None
    for line in hlo_text.splitlines():
        m = _COMP.match(line)
        if m:
            comp = m.group(1)
            continue
        m = _INSTR.match(line)
        if comp is None or not m:
            continue
        name, _, opcode, rest = m.groups()
        operands, attrs = _split(rest)
        op_name = _OP_NAME.search(attrs)
        out[name] = (comp, opcode, op_name.group(1) if op_name else "",
                     _REF.findall(operands), _REF.findall(attrs))
    return out


def op_scopes(hlo_text: str) -> Dict[str, str]:
    """``{instruction: op_name}`` of a module's HLO text. An instruction
    without an op_name (a layout copy, a bitcast, a tuple the compiler
    made) takes that of its nearest user that has one, the first in
    instruction order, within its computation; failing that, that of the
    instruction calling its computation (the ``conditional`` of a branch,
    the ``while`` of a body); ``""`` where neither gives one."""
    ops = hlo_ops(hlo_text)
    comps = {v[0] for v in ops.values()}
    users: Dict[str, List[str]] = {}
    caller: Dict[str, str] = {}
    for name, (comp, _, _, refs, called) in ops.items():
        for r in dict.fromkeys(refs):
            if r in ops and ops[r][0] == comp:
                users.setdefault(r, []).append(name)
        for c in called:
            if c in comps:
                caller.setdefault(c, name)
    out = {}
    for name, (_, _, op_name, _, _) in ops.items():
        seen, level = {name}, [name]
        while not op_name and level:
            nxt = []
            for f in level:
                for u in users.get(f, ()):
                    if u not in seen:
                        seen.add(u)
                        nxt.append(u)
            level = nxt
            op_name = next((ops[u][2] for u in level if ops[u][2]), "")
        out[name] = op_name
    for name in out:
        n = name
        while not out[name] and ops[n][0] in caller:
            n = caller[ops[n][0]]
            out[name] = out[n]
    return out


def scope_names(op_name: str) -> Tuple[Set[str], bool]:
    """The scope names on an op_name's path, each with the transformations
    around it taken off (``transpose(jvp(vmap(model)))`` is ``model``), and
    whether the op is a transpose, i.e. of the backward pass. Of a fused
    op's ``a;b`` names the first, its root's, counts; a nested jit's
    function and the path's last part, the primitive, are not scopes."""
    path = op_name.split(";", 1)[0]
    names, backward = set(), False
    for part in path.split("/")[:-1]:
        if _CALL.match(part):
            continue
        backward = backward or "transpose" in _WRAPPER.findall(part)
        names.add(part[part.rfind("(") + 1:].rstrip(")"))
    return names, backward


# ------------------------------ the step ------------------------------------

def same_op(a: str, b: str) -> bool:
    """Whether two opcodes name the same op: an event of the trace reads
    ``async-start`` where the HLO text reads ``slice-start``, say."""
    if a == b or not a or not b:
        return True
    a, b = sorted((a, b))
    return a.startswith("async-") and b.endswith(a[5:])


def _inside(runs: List[Tuple[float, float]], starts: List[float],
            t: float) -> bool:
    i = bisect.bisect_right(starts, t) - 1
    return i >= 0 and t < runs[i][1]


@dataclasses.dataclass
class StepProgram:
    scopes: Dict[str, Tuple[str, str]]    # instruction -> (opcode, op_name)
    runs: Runs                            # the step's runs, sorted

    def ops(self, device: trace.Device
            ) -> List[Tuple[trace.Op, float, Set[str], bool]]:
        """(op, self time ns, scope names, backward) of each op of the
        step program on one chip."""
        runs = self.runs.get(device.id, [])
        starts = [s for s, _ in runs]
        out = []
        for op, t in zip(device.ops, trace.self_times(device.ops)):
            known = self.scopes.get(op.name)
            if (known is None or not same_op(known[0], op.kind)
                    or not _inside(runs, starts, op.start)):
                continue
            names, backward = scope_names(known[1])
            out.append((op, t, names, backward))
        return out

    def has_scope(self, name: str) -> bool:
        return any(name in scope_names(n)[0] for _, n in self.scopes.values())


def module_runs(tr: trace.Trace, root: pathlib.Path = TRACE_ROOT
                ) -> Dict[str, Runs]:
    """``{module name with its id: runs}`` from the ``XLA Modules`` lines
    of the recording under ``root`` that holds ``tr``'s window (its host
    span ``WINDOW`` starts where ``tr``'s does); ``{}`` if none does."""
    from jax.profiler import ProfileData

    files = sorted(root.glob("**/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime, reverse=True)
    for path in files:
        data = ProfileData.from_file(str(path))
        out: Dict[str, Runs] = {}
        window = None
        for plane in data.planes:
            if plane.name.startswith("/host:CPU"):
                window = next((ev.start_ns for line in plane.lines
                               for ev in line.events
                               if ev.name == trace.WINDOW), window)
            m = trace._TPU_PLANE.match(plane.name)
            for line in plane.lines if m else ():
                if line.name != MODULES_LINE:
                    continue
                for ev in line.events:
                    out.setdefault(ev.name, {}).setdefault(
                        int(m.group(1)), []).append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns))
        if window == tr.window[0]:
            return out
    return {}


def find_step(tr: trace.Trace, runs: Dict[str, Runs],
              texts_of: Callable[[str], Iterable[str]]
              ) -> Optional[StepProgram]:
    """The program that ran longest in ``tr``'s window, with its scopes
    from the first of ``texts_of(module name)`` (HLO texts) that is its
    module: every instruction its runs executed is defined there with the
    same opcode."""
    if not runs:
        return None
    w0, w1 = tr.window

    def in_window(name):
        return sum(max(0.0, min(e, w1) - max(s, w0))
                   for rs in runs[name].values() for s, e in rs)

    step = max(runs, key=in_window)
    step_runs = {c: sorted(r) for c, r in runs[step].items()}
    executed = set()
    for d in tr.devices:
        r = step_runs.get(d.id, [])
        starts = [s for s, _ in r]
        executed |= {(op.name, op.kind) for op in d.ops
                     if _inside(r, starts, op.start)}
    m = _MODULE.match(step)
    for text in texts_of(m.group(1) if m else step):
        ops = hlo_ops(text)
        if all(n in ops and same_op(ops[n][1], k) for n, k in executed):
            scopes = op_scopes(text)
            return StepProgram({n: (ops[n][1], scopes[n]) for n in ops},
                               step_runs)
    return None


def live_modules(name: str) -> Iterable[str]:
    """HLO texts of the executables this process holds whose module is
    named ``name``."""
    import jax

    for ex in jax.devices()[0].client.live_executables():
        for mod in ex.hlo_modules():
            if mod.name == name:
                yield mod.to_string()


def step_program(ctx) -> Optional[StepProgram]:
    """The step program of the traced window, worked out once per context.
    A test sets ``ctx.step_program`` itself."""
    if not hasattr(ctx, "step_program"):
        ctx.step_program = find_step(ctx.trace, module_runs(ctx.trace),
                                     live_modules)
    return ctx.step_program


def step_ops(device, ctx, scope: str, keep
             ) -> Optional[List[Tuple[trace.Op, float]]]:
    """(op, self time ns) of the step's ops on one chip that ``keep(op,
    names, backward)`` accepts; None where no op of the step is under
    ``scope``."""
    prog = step_program(ctx)
    if prog is None or not prog.has_scope(scope):
        return None
    return [(op, t) for op, t, names, bwd in prog.ops(device)
            if keep(op, names, bwd)]


def scoped_ms(device, ctx, scope: str, keep) -> Optional[float]:
    """Self time per step, ms, of the step's ops that ``keep(op, names,
    backward)`` accepts; None where no op of the step is under ``scope``."""
    ops = step_ops(device, ctx, scope, keep)
    if ops is None or not ctx.steps:
        return None
    return sum(t for _, t in ops) / ctx.steps / 1e6


def opt_kernels(device, ctx) -> List[trace.Op]:
    """The step's Mosaic kernel events on one chip that lie under the
    program's ``opt`` scope: the optimizer's kernels, and not a kernel
    of the model's."""
    ops = step_ops(device, ctx, "opt", lambda op, names, bwd:
                   "opt" in names and ctx.is_kernel(op))
    return [op for op, _ in ops or ()]


# ------------------------------ host spans ----------------------------------

def idle_in_spans_ms(device: trace.Device, ctx, span: str
                     ) -> Optional[float]:
    """The chip's idle time inside the window's host spans named ``span``,
    per step, ms; None where the window holds no such span."""
    w0, w1 = ctx.trace.window
    spans = [(max(h.start, w0), min(h.end, w1))
             for h in ctx.trace.host if h.name == span]
    if not spans or not ctx.steps:
        return None
    busy: List[List[float]] = []
    for o in device.ops:                 # sorted by start
        if busy and o.start <= busy[-1][1]:
            busy[-1][1] = max(busy[-1][1], o.end)
        else:
            busy.append([o.start, o.end])
    starts = [s for s, _ in busy]
    idle = 0.0
    for s, e in spans:
        covered = 0.0
        i = max(bisect.bisect_right(starts, s) - 1, 0)
        while i < len(busy) and busy[i][0] < e:
            covered += max(0.0, min(e, busy[i][1]) - max(s, busy[i][0]))
            i += 1
        idle += max(0.0, e - s - covered)
    return idle / ctx.steps / 1e6
