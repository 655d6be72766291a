"""The per-layer readers and the breakdown against a small recorded
trace: three steps of the DeepFM cell on a TPU v5 lite, cut from a
``--trace 1`` run (``data/deepfm-3-steps.json``: the ``XLA Ops`` events
by instruction and opcode, the host spans, the kernel calls of the
compiled step; the optimizer kernels' readers, which need the step's
scopes, against ``data/deepfm-3-steps-scoped.json``). The expected
values are worked out here by other means: a busy mask over the window
at 10 ns resolution, and sums over the events by kind."""
from __future__ import annotations

import numpy as np
import pytest

from chipbench import layers, spec, trace
from recorded import FLOPS_PER_STEP, context, load

RES = 10.0                        # ns per bin of the mask


@pytest.fixture(scope="module")
def recorded():
    fix = load("deepfm-3-steps.json")
    return fix, context(fix)


@pytest.fixture(scope="module")
def scoped():
    """The recording of a program with scopes: the optimizer's kernels
    are those under ``opt``."""
    fix = load("deepfm-3-steps-scoped.json")
    return fix, context(fix, scoped=True)


def _mask(fix, keep):
    w0, w1 = fix["window"]
    m = np.zeros(int((w1 - w0) / RES) + 1, bool)
    for n, k, s, e in fix["ops"]:
        if keep(n, k):
            m[int((s - w0) / RES):int((e - w0) / RES)] = True
    return m


def test_idle_share_and_busy_time(recorded):
    fix, ctx = recorded
    busy = _mask(fix, lambda n, k: True).sum() * RES
    window = fix["window"][1] - fix["window"][0]
    got = layers.read_all(["device_idle_share"], ctx)["device_idle_share"]
    assert got == pytest.approx(100 * (1 - busy / window), abs=0.05)
    assert 0 < got < 5


def test_kernel_time_and_roofline(scoped):
    fix, ctx = scoped
    kernels = [(n, e - s) for n, k, s, e in fix["ops"] if n in fix["calls"]]
    assert len(kernels) == 3          # fused_adam twice, the gossip once
    want_ms = sum(d for _, d in kernels) / 3 / 1e6
    got = layers.read_all(["opt_kernel_ms", "opt_kernel_roofline"], ctx)
    assert got["opt_kernel_ms"] == pytest.approx(want_ms, rel=1e-9)
    # each call moves 7 distinct (rows, 128) f32 buffers: 4 in, 3 out
    least = 0.0
    for n, d in kernels:
        elems = int(np.prod(fix["calls"][n]["results"][0][1]))
        least += 7 * 4 * elems / 819e9
    want = 100 * least / (sum(d for _, d in kernels) / 1e9)
    assert got["opt_kernel_roofline"] == pytest.approx(want, rel=1e-9)
    assert 0 < got["opt_kernel_roofline"] <= 100


def test_xla_ops_exclude_kernels_inside_conditionals(recorded):
    fix, ctx = recorded
    busy = _mask(fix, lambda n, k: True)
    kern = _mask(fix, lambda n, k: n in fix["calls"])
    want = (busy & ~kern).sum() * RES / 3 / 1e6
    got = layers.read_all(["xla_ops_ms"], ctx)["xla_ops_ms"]
    assert got == pytest.approx(want, rel=1e-3)


def test_mfu_and_no_collectives(recorded):
    fix, ctx = recorded
    got = layers.read_all(["mfu", "collective_exposed_ms"], ctx)
    step_s = (fix["window"][1] - fix["window"][0]) / 1e9 / 3
    assert got["mfu"] == pytest.approx(100 * FLOPS_PER_STEP
                                       / (step_s * 197e12), rel=1e-9)
    assert got["collective_exposed_ms"] is None


def test_breakdown_uses_self_time(recorded):
    fix, ctx = recorded
    out = layers.breakdown(ctx)
    names = [n for n, _ in out["device_ops"]]
    assert len(names) == 10 and not any("conditional" in n for n in names)
    total = sum(t for _, t in out["device_ops"])
    window = (fix["window"][1] - fix["window"][0]) / 1e9
    assert total <= window
    assert all(t > 0 for _, t in out["idle_gaps"])


def test_collective_exposed_counts_only_time_without_compute():
    ops = [trace.Op("cond.1", 0, 100, "conditional"),
           trace.Op("fusion.1", 0, 40, "fusion"),
           trace.Op("collective-permute-start.1", 30, 70,
                    "collective-permute-start"),
           trace.Op("fusion.2", 60, 65, "fusion")]
    ctx = layers.Context(trace.Trace([trace.Device(0, ops)], (0, 100), []),
                         1, spec.peaks_for("TPU v5 lite"), 1.0, {}, {})
    got = layers.read_all(["collective_exposed_ms"], ctx)
    # 30-70 less the compute at 30-40 and 60-65; the conditional's span
    # is not compute
    assert got["collective_exposed_ms"] == pytest.approx(25 / 1e6)


def test_self_times_subtract_nested_events():
    ops = [trace.Op("while.1", 0, 100, "while"),
           trace.Op("fusion.1", 10, 30, "fusion"),
           trace.Op("cond.1", 40, 90, "conditional"),
           trace.Op("kernel", 50, 80, "custom-call"),
           trace.Op("copy.1", 120, 130, "copy")]
    assert trace.self_times(ops) == [30, 20, 20, 30, 10]
    assert trace.union_ns((o.start, o.end) for o in ops) == 110
