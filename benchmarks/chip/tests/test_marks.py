"""The readers of the program's own marks (``chipbench/marks.py``): the
compiled step's scopes and the trainer's host spans.

Against ``data/deepfm-3-steps-scoped.json``, three steps of the DeepFM
cell on a TPU v5 lite cut from a ``--trace 1`` run of a program with
scopes and spans (the ``XLA Ops`` events, the host spans, the kernel
calls, the step program's runs and each of its instructions' opcode and
scope path), the readers are checked against values worked out by other
means: a mask over the window at 10 ns resolution in which each bin
belongs to the innermost op covering it. On the older recording of a
program without marks (``data/deepfm-3-steps.json``) the readers of
scopes and spans report nothing and the others read what they read
before."""
from __future__ import annotations

import numpy as np
import pytest

from chipbench import layers, marks, trace
from recorded import context, load

RES = 10.0                        # ns per bin of the mask
NEW = ["fwd_ms", "bwd_ms", "packed_view_ms", "opt_glue_ms", "gossip_ms",
       "log_idle_ms", "dispatch_idle_ms"]


@pytest.fixture(scope="module")
def scoped():
    fix = load("deepfm-3-steps-scoped.json")
    return fix, context(fix, scoped=True)


def _innermost_ms(fix, keep, step=True):
    """Per step, ms: bins whose innermost op (the latest to start among
    those covering the bin) is a step op that ``keep(name, opcode,
    op_name)`` accepts; with ``step=False``, an op of another program."""
    w0, w1 = fix["window"]
    owner = np.full(int((w1 - w0) / RES) + 1, -1)
    ops = sorted(fix["ops"], key=lambda o: (o[2], -o[3]))
    for i, (n, k, s, e) in enumerate(ops):
        owner[int((s - w0) / RES):int((e - w0) / RES)] = i
    runs = fix["runs"]
    take = np.zeros(len(ops) + 1, bool)
    for i, (n, k, s, e) in enumerate(ops):
        known = fix["scopes"].get(n)
        in_step = any(a <= s < b for a, b in runs)
        if not step:
            take[i] = not in_step
            continue
        take[i] = bool(known and marks.same_op(known[0], k) and in_step
                       and keep(n, k, known[1]))
    return take[owner].sum() * RES / fix["steps"] / 1e6


def _parts(op_name):
    return [p.split("(")[-1].rstrip(")")
            for p in op_name.split(";")[0].split("/")[1:-1]]


def test_scoped_readers_against_a_mask(scoped):
    fix, ctx = scoped
    view = {"unpack", "pack"}

    def model(bwd):
        return lambda n, k, o: ("model" in _parts(o)
                                and ("transpose(" in o.split(";")[0]) == bwd
                                and not view & set(_parts(o)))

    want = {
        "fwd_ms": _innermost_ms(fix, model(False)),
        "bwd_ms": _innermost_ms(fix, model(True)),
        "packed_view_ms": _innermost_ms(
            fix, lambda n, k, o: bool(view & set(_parts(o)))),
        "opt_glue_ms": _innermost_ms(
            fix, lambda n, k, o: "opt" in _parts(o) and n not in fix["calls"]
            and not view & set(_parts(o))),
        "gossip_ms": _innermost_ms(fix, lambda n, k, o: "gossip" in _parts(o)),
    }
    got = layers.read_all(list(want), ctx)
    for name, v in want.items():
        assert got[name] == pytest.approx(v, rel=2e-3, abs=2e-4), name
    assert got["packed_view_ms"] > got["fwd_ms"] > 0
    # the four layers split the time outside the kernels, less the log
    # point's eager programs (a third of these three steps' time)
    xla = layers.read_all(["xla_ops_ms"], ctx)["xla_ops_ms"]
    eager = _innermost_ms(fix, lambda n, k, o: True, step=False)
    split = (got["fwd_ms"] + got["bwd_ms"] + got["packed_view_ms"]
             + got["opt_glue_ms"])
    assert eager > 0.1 * xla
    assert 0.99 * (xla - eager) <= split <= xla - eager


def test_idle_readers_against_a_mask(scoped):
    fix, ctx = scoped
    w0, w1 = fix["window"]
    n = int((w1 - w0) / RES) + 1
    busy = np.zeros(n, bool)
    for _, _, s, e in fix["ops"]:
        busy[int((s - w0) / RES):int((e - w0) / RES)] = True
    got = layers.read_all(["log_idle_ms", "dispatch_idle_ms"], ctx)
    for name, span in (("log_idle_ms", "fit.log"),
                       ("dispatch_idle_ms", "fit.dispatch")):
        inside = np.zeros(n, bool)
        for h, s, e in fix["host"]:
            if h == span:
                inside[max(int((s - w0) / RES), 0):int((e - w0) / RES)] = True
        want = (inside & ~busy).sum() * RES / fix["steps"] / 1e6
        assert got[name] == pytest.approx(want, rel=1e-2, abs=1e-4), name


def test_opt_kernel_readers_count_only_the_opt_scope(scoped):
    """Every kernel of the recorded step is the optimizer's: the readings
    equal those of the readers that counted every Mosaic kernel. A
    kernel put under ``model`` drops out of both."""
    fix, ctx = scoped
    names = ["opt_kernel_ms", "opt_kernel_roofline"]
    old = {"opt_kernel_ms": 6.43939,                  # the readers before
           "opt_kernel_roofline": 68.64934796406567}  # the opt scope
    got = layers.read_all(names, ctx)
    for n in names:
        assert got[n] == pytest.approx(old[n], rel=1e-12), n
    prog = ctx.step_program
    gossip = [o for o in ctx.trace.devices[0].ops if o.name == "gossip.1"]
    assert len(gossip) == 1
    ctx.step_program = marks.StepProgram(
        dict(prog.scopes, **{"gossip.1": (
            "custom-call", "jit(step)/grad/model/pallas_call")}), prog.runs)
    try:
        moved = layers.read_all(names, ctx)
    finally:
        ctx.step_program = prog
    assert moved["opt_kernel_ms"] == pytest.approx(
        old["opt_kernel_ms"] - gossip[0].dur / fix["steps"] / 1e6,
        rel=1e-12)
    assert moved["opt_kernel_roofline"] != pytest.approx(
        old["opt_kernel_roofline"], rel=1e-6)


def test_old_recording_new_readers_silent_others_unchanged():
    """The optimizer kernels' readers need the ``opt`` scope too."""
    ctx = context(load("deepfm-3-steps.json"))   # a program without scopes
    silent = NEW + ["opt_kernel_ms", "opt_kernel_roofline"]
    got = layers.read_all(silent, ctx)
    assert got == {n: None for n in silent}
    before = {"device_idle_share": 0.008367200329262303,
              "xla_ops_ms": 99.48147233333333,
              "mfu": 0.05629642234200215, "collective_exposed_ms": None}
    got = layers.read_all(list(before), ctx)
    for name, v in before.items():
        assert got[name] == (v if v is None else pytest.approx(v, rel=1e-12))


HLO = """HloModule jit_step

%branch (p: f32[4]) -> (f32[4]) {
  %p = f32[4]{0} parameter(0)
  %copy.2 = f32[4]{0} copy(%p)
  ROOT %tuple.2 = (f32[4]{0}) tuple(%copy.2)
}

ENTRY %main (a: f32[4], i: s32[]) -> f32[4] {
  %a = f32[4]{0} parameter(0), metadata={op_name="state[0]"}
  %i = s32[] parameter(1)
  %copy.1 = f32[4]{0} copy(%a)
  %bitcast.1 = f32[4]{0} bitcast(%copy.1)
  %tuple.1 = (f32[4]{0}) tuple(%bitcast.1)
  %c = (f32[4]{0}) conditional(%i, %tuple.1), branch_computations={%branch}, metadata={op_name="jit(step)/opt/cond"}
  %fusion.1 = f32[4]{0} fusion(%bitcast.1, %bitcast.1), kind=kLoop, calls=%branch, metadata={op_name="jit(step)/grad/transpose(jvp(vmap(model)))/add"}
  ROOT %gte = f32[4]{0} get-tuple-element(%c), index=0
}
"""


def test_op_scopes_takes_a_user_or_caller_scope():
    s = marks.op_scopes(HLO)
    assert s["a"] == "state[0]"
    # the nearest user with an op_name, breadth first: fusion.1 two hops
    # from copy.1 (through the bitcast), the conditional three
    want = "jit(step)/grad/transpose(jvp(vmap(model)))/add"
    assert s["copy.1"] == s["bitcast.1"] == want
    assert s["tuple.1"] == "jit(step)/opt/cond"
    # the branch's copy has no user with one: the first caller's
    assert s["copy.2"] == s["tuple.2"] == "jit(step)/opt/cond"
    assert s["gte"] == ""


def test_scope_names():
    assert marks.scope_names(
        "jit(step)/grad/transpose(jvp(vmap(model)))/dot_general") == (
            {"grad", "model"}, True)
    assert marks.scope_names(
        "jit(step)/opt/cond/branch_1_fun/gossip/jit(_roll)/slice;"
        "jit(step)/grad/x") == ({"opt", "cond", "branch_1_fun", "gossip"},
                                False)
    assert marks.scope_names("state[0]") == (set(), False)


def test_find_step_keeps_out_programs_with_colliding_names():
    # jit_step runs at 0-100 and 200-300, an eager jit_add at 120-140;
    # both have a fusion.1 and a copy.1
    ops = [trace.Op("copy.1", 0, 10, "copy"),
           trace.Op("fusion.1", 10, 90, "fusion"),
           trace.Op("fusion.1", 120, 140, "fusion"),
           trace.Op("copy.1", 200, 210, "copy"),
           trace.Op("fusion.1", 210, 290, "fusion")]
    tr = trace.Trace([trace.Device(0, ops)], (0, 300), [])
    runs = {"jit_step(42)": {0: [(200, 300), (0, 100)]},
            "jit_add(7)": {0: [(120, 140)]}}
    texts = {"jit_step": ["HloModule jit_add\nENTRY %m () -> f32[] {\n"
                          "  %fusion.1 = f32[] fusion(), kind=kLoop\n}\n",
                          HLO]}
    prog = marks.find_step(tr, runs, lambda name: texts.get(name, []))
    assert prog.runs == {0: [(0, 100), (200, 300)]}
    got = [(op.start, names, bwd) for op, t, names, bwd in prog.ops(tr.devices[0])]
    assert got == [(0, {"grad", "model"}, True), (10, {"grad", "model"}, True),
                   (200, {"grad", "model"}, True),
                   (210, {"grad", "model"}, True)]
    # a module that lacks an executed instruction is not the step's
    assert marks.find_step(tr, runs, lambda name: texts["jit_step"][:1]) is None


def test_module_runs_reads_the_recording_of_the_window(tmp_path):
    from jax.profiler import ProfileData

    def xspace(window_ps):
        return ProfileData.text_proto_to_serialized_xspace(f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 1000
    events {{ metadata_id: 1 offset_ps: 0 duration_ps: 5000000 }}
    events {{ metadata_id: 2 offset_ps: 6000000 duration_ps: 1000000 }} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "jit_step(42)" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "jit_add(7)" }} }} }}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 900
    events {{ metadata_id: 1 offset_ps: {window_ps} duration_ps: 9000000 }} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "{trace.WINDOW}" }} }} }}
""")

    for cell, at in (("a", 0), ("b", 50000)):
        d = tmp_path / cell / "plugins" / "profile" / "1"
        d.mkdir(parents=True)
        (d / "host.xplane.pb").write_bytes(xspace(at))
    tr = trace.Trace([], (900.0, 9900.0), [])
    assert marks.module_runs(tr, tmp_path) == {
        "jit_step(42)": {0: [(1000.0, 6000.0)]},
        "jit_add(7)": {0: [(7000.0, 8000.0)]}}
    assert marks.module_runs(trace.Trace([], (1.0, 2.0), []), tmp_path) == {}
