"""Per-layer metrics from the traced window: one reader file per metric
under ``benchmarks/chip/metrics/``, found by the metric's name.

A reader's ``read(device, ctx)`` returns the metric for one chip, or
``None`` where it finds nothing to read (the harness then leaves the
metric out of the line). On several chips the metric is the largest
over them; the per-chip values are printed on an earlier line.
"""
from __future__ import annotations

import dataclasses
import sys
from typing import Dict, List, Optional

from chipbench import spec, trace


@dataclasses.dataclass
class Context:
    trace: trace.Trace
    steps: int                      # optimizer steps in the traced window
    peaks: dict
    flops_per_step: float           # model FLOPs of one step, all chips
    calls: Dict[str, trace.KernelCall]
    costs: Dict[str, object]        # kernel name -> cost module
    warned: set = dataclasses.field(default_factory=set)

    @property
    def chips(self) -> int:
        return len(self.trace.devices)

    def is_kernel(self, op: trace.Op) -> bool:
        return op.name in self.calls

    def kernel_cost(self, op: trace.Op):
        """(flops, bytes) of one kernel event; (0, 0) with a warning line
        for a kernel that no cost file knows."""
        call = self.calls[op.name]
        mod = self.costs.get(call.kernel)
        if mod is None:
            if op.name not in self.warned:
                self.warned.add(op.name)
                print(f"[layers] warning: kernel call {op.name} "
                      f"({call.kernel or 'unnamed'}) has no cost file under "
                      f"benchmarks/chip/kernels/; its time counts with zero "
                      f"bytes", file=sys.stderr)
            return 0.0, 0.0
        return mod.cost(call)


def kernel_modules() -> Dict[str, object]:
    return {p.stem: spec.load_module(p)
            for p in sorted((spec.HERE / "kernels").glob("*.py"))}


def read_all(names: List[str], ctx: Context) -> Dict[str, Optional[float]]:
    out = {}
    for name in names:
        mod = spec.load_module(spec.HERE / "metrics" / f"{name}.py")
        per_chip = [mod.read(d, ctx) for d in ctx.trace.devices]
        vals = [v for v in per_chip if v is not None]
        if len(ctx.trace.devices) > 1:
            print(f"[layers] {name} per chip "
                  f"{[None if v is None else round(v, 6) for v in per_chip]}",
                  file=sys.stderr)
        out[name] = max(vals) if vals else None
    return out


def _label(op: trace.Op, ctx: Context) -> str:
    call = ctx.calls.get(op.name)
    if call:
        return f"{call.kernel or 'kernel'} ({op.name})"
    return f"{op.name} ({op.kind})" if op.kind else op.name


def _gaps(device: trace.Device, window) -> List[tuple]:
    """Idle intervals of one chip inside the window."""
    gaps, cur = [], window[0]
    for o in device.ops:
        if o.start > cur:
            gaps.append((cur, o.start))
        cur = max(cur, o.end)
    if window[1] > cur:
        gaps.append((cur, window[1]))
    return gaps


def _host_doing(gap, host: List[trace.Op]) -> str:
    """The host span that overlaps the gap most (the innermost on a tie)."""
    best, best_ov, best_dur = "no host span", 0.0, float("inf")
    for h in host:
        ov = min(gap[1], h.end) - max(gap[0], h.start)
        if ov > best_ov or (ov == best_ov and ov > 0 and h.dur < best_dur):
            best, best_ov, best_dur = h.name, ov, h.dur
    return best


def breakdown(ctx: Context, top: int = 10) -> dict:
    """The device operations that took most time (self time, seconds per
    chip over the traced window) and the longest idle gaps, each named by
    what the host was doing in it."""
    tr = ctx.trace
    tot: Dict[str, float] = {}
    for d in tr.devices:
        for o, t in zip(d.ops, trace.self_times(d.ops)):
            k = _label(o, ctx)
            tot[k] = tot.get(k, 0.0) + t / 1e9 / len(tr.devices)
    ops = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    gaps = []
    for d in tr.devices:
        gaps += [(g, d.id) for g in _gaps(d, tr.window)]
    gaps.sort(key=lambda gd: -(gd[0][1] - gd[0][0]))
    idle = [[f"chip {dev}: {_host_doing(g, tr.host)}", (g[1] - g[0]) / 1e9]
            for g, dev in gaps[:top]]
    return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": idle}
