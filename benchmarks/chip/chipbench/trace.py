"""From a profiler trace to the device operations of the measured window.

``capture`` wraps the window in ``jax.profiler`` tracing and a host span
named ``WINDOW``. ``load`` reads the ``.xplane.pb`` with JAX's own
``ProfileData`` and keeps, for each TPU, the events of its ``XLA Ops``
line that start inside the window; an event is named by its HLO
instruction, and control-flow ops (``while``, ``conditional``) span the
events of their bodies. ``kernel_calls`` reads the compiled
step's HLO text to tell which Pallas kernel each ``tpu_custom_call``
instruction runs and with which operand and result shapes.
"""
from __future__ import annotations

import base64
import contextlib
import dataclasses
import pathlib
import re
import shutil
from typing import Dict, Iterable, List, Optional, Tuple

WINDOW = "bench_window"
OPS_LINE = "XLA Ops"
_TPU_PLANE = re.compile(r"^/device:TPU:(\d+)$")
COLLECTIVES = ("collective-permute", "all-reduce", "all-gather",
               "reduce-scatter", "all-to-all", "send", "recv")


@dataclasses.dataclass
class Op:
    name: str            # HLO instruction name, without the '%'
    start: float         # ns
    end: float           # ns
    kind: str = ""       # HLO opcode ('fusion', 'custom-call', 'while', ...)

    @property
    def dur(self) -> float:
        return self.end - self.start


_EVENT = re.compile(r"^%?([^\s=]+)\s*=\s*.*?\s([a-z][a-z0-9-]*)\(")


def parse_event(text: str) -> Tuple[str, str]:
    """(instruction name, opcode) of an ``XLA Ops`` event, whose name is
    the instruction's HLO text (``%fusion.3 = f32[...] fusion(...)``)."""
    m = _EVENT.match(text)
    if m:
        return m.group(1), m.group(2)
    return text.split(" ", 1)[0].lstrip("%"), ""


@dataclasses.dataclass
class Device:
    id: int
    ops: List[Op]


@dataclasses.dataclass
class Trace:
    devices: List[Device]
    window: Tuple[float, float]       # ns, the host span WINDOW
    host: List[Op]                    # host spans inside the window

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]


@contextlib.contextmanager
def capture(directory: pathlib.Path):
    import jax

    if directory.exists():
        shutil.rmtree(directory)
    directory.mkdir(parents=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0      # no span per Python call
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(directory), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(WINDOW):
            yield
    finally:
        jax.profiler.stop_trace()


def load(directory: pathlib.Path) -> Trace:
    from jax.profiler import ProfileData

    files = sorted(directory.glob("**/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    data = ProfileData.from_file(str(files[-1]))
    window = None
    devices, host = [], []
    for plane in data.planes:
        m = _TPU_PLANE.match(plane.name)
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    else:
                        host.append(Op(ev.name, ev.start_ns,
                                       ev.start_ns + ev.duration_ns))
        if not m:
            continue
        ops = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                name, kind = parse_event(ev.name)
                ops.append(Op(name, ev.start_ns,
                              ev.start_ns + ev.duration_ns, kind))
        devices.append(Device(int(m.group(1)), ops))
    if window is None:
        raise ValueError(f"no {WINDOW!r} host span in the trace")
    if not any(d.ops for d in devices):
        raise ValueError(f"no TPU operation on an {OPS_LINE!r} line of the "
                         f"trace (planes: {[p.name for p in data.planes]})")
    for d in devices:
        d.ops = sorted((o for o in d.ops if window[0] <= o.start < window[1]),
                       key=lambda o: (o.start, -o.end))
    host = [o for o in host if o.end > window[0] and o.start < window[1]]
    return Trace(sorted(devices, key=lambda d: d.id), window, host)


def union_ns(intervals: Iterable[Tuple[float, float]]) -> float:
    """Length of the union of [start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def is_collective(op: Op) -> bool:
    return any(op.kind.startswith(c) for c in COLLECTIVES)


# ops whose event spans the events of the computations they run
CONTROL_FLOW = ("while", "conditional", "call")


def self_times(ops: List[Op]) -> List[float]:
    """Each op's duration less the time of the ops nested inside it (a
    ``while`` or ``conditional`` event spans its body's events)."""
    out = [o.dur for o in ops]
    stack: List[int] = []
    for i, o in enumerate(ops):
        while stack and ops[stack[-1]].end <= o.start:
            stack.pop()
        if stack:
            out[stack[-1]] -= min(o.end, ops[stack[-1]].end) - o.start
        stack.append(i)
    return out


# ----------------------------- kernel calls ---------------------------------

_SHAPE = re.compile(r"(pred|s8|u8|s16|u16|s32|u32|s64|u64|bf16|f16|f32|f64)"
                    r"\[([0-9,]*)\]")
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
          "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
          "f64": 8}
_CALL = re.compile(r"^\s*(?:ROOT\s+)?%(\S+)\s*=\s*(.*?)\s+custom-call\((.*?)\),"
                   r".*custom_call_target=\"tpu_custom_call\".*\"body\":\"([^\"]*)\"")


@dataclasses.dataclass(frozen=True)
class Array:
    dtype: str
    shape: Tuple[int, ...]

    @property
    def size(self) -> int:
        n = 1
        for d in self.shape:
            n *= d
        return n

    @property
    def nbytes(self) -> int:
        return self.size * _BYTES[self.dtype]


@dataclasses.dataclass(frozen=True)
class KernelCall:
    instruction: str
    kernel: Optional[str]             # None: no cost file knows it
    operands: Tuple[Array, ...]       # distinct operands (aliases once)
    results: Tuple[Array, ...]
    arity: int                        # operands as passed, aliases too


def _arrays(text: str) -> List[Array]:
    return [Array(dt, tuple(int(x) for x in dims.split(",") if x))
            for dt, dims in _SHAPE.findall(text)]


def kernel_calls(hlo_text: str, known: Iterable[str]
                 ) -> Dict[str, KernelCall]:
    """``{instruction name: KernelCall}`` for every Mosaic kernel call in a
    compiled module. The kernel is the longest of ``known`` names that
    the call's serialized body holds as a whole word."""
    known = sorted(known, key=len, reverse=True)
    pats = [(k, re.compile(rb"(?<![A-Za-z0-9_])" + re.escape(k.encode())
                           + rb"(?![A-Za-z0-9_])")) for k in known]
    shapes = {}
    for line in hlo_text.splitlines():
        m = re.match(r"^\s*(?:ROOT\s+)?%(\S+)\s*=\s*(\S+?\[[0-9,]*\])", line)
        if m:
            shapes[m.group(1)] = _arrays(m.group(2))
    calls = {}
    for line in hlo_text.splitlines():
        m = _CALL.match(line)
        if not m:
            continue
        name, result_text, operand_text, body = m.groups()
        blob = base64.b64decode(body)
        kernel = next((k for k, p in pats if p.search(blob)), None)
        seen, operands = set(), []
        refs = re.findall(r"%([^\s,)]+)", operand_text)
        for ref in refs:
            if ref not in seen and ref in shapes:
                seen.add(ref)
                operands.extend(shapes[ref])
        calls[name] = KernelCall(name, kernel, tuple(operands),
                                 tuple(_arrays(result_text)), len(refs))
    return calls
