"""The program's marks in a profile: the named scopes of the compiled
training step (``repro.tracing``) and the host spans of
``DecentralizedTrainer.fit``."""
from __future__ import annotations

import pathlib
import re

import jax
import jax.numpy as jnp
import pytest

from repro.analysis.hlo import op_scopes, parse_hlo
from repro.core import make_optimizer
from repro.train.loop import DecentralizedTrainer

SCOPES = {"grad", "model", "unpack", "pack", "opt", "adam", "gossip",
          "compress", "damping"}
COUNTED = ("fusion", "custom-call", "copy", "collective-permute",
           "all-reduce", "all-gather", "reduce-scatter", "all-to-all")
VARIANTS = [(o, b) for o in ("d-adam", "cd-adam")
            for b in ("pallas", "reference")]


def _loss(p, b):
    h = jnp.tanh(b["x"] @ p["w1"])
    return jnp.mean((h @ p["w2"] - b["y"]) ** 2)


def _trainer(name, backend, **kw):
    okw = dict(period=2, topology="ring", backend=backend)
    if name == "cd-adam":
        okw["compressor"] = "sign"
    tr = DecentralizedTrainer(_loss, make_optimizer(name, K=2, eta=1e-2,
                                                    **okw), **kw)
    state = tr.init({"w1": jnp.zeros((8, 16)), "w2": jnp.zeros((16, 1))})
    return tr, state


def _batches():
    while True:
        yield {"x": jnp.ones((2, 4, 8)), "y": jnp.ones((2, 4, 1))}


def _names(op_name):
    """Scope names on an op_name's path, transformations taken off."""
    return {re.sub(r".*\(", "", p).rstrip(")")
            for p in op_name.split(";")[0].split("/")[:-1]}


@pytest.fixture(scope="module", params=VARIANTS, ids="-".join)
def compiled_step(request):
    # a cache entry compiled before a scope moved has the same key (the
    # key leaves out debug info) and the old op_names: compile afresh
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        tr, state = _trainer(*request.param)
        text = tr.lower_step(state, next(_batches())).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
    return request.param, text


def test_step_carries_each_layer_scope(compiled_step):
    (name, backend), text = compiled_step
    parts = {tuple(n.split(";")[0].split("/")[1:-1])
             for n in op_scopes(text).values() if n.startswith("jit(step)/")}

    def has(scope, outer, *, transpose=False):
        """Some op under ``outer`` whose next part wraps ``scope`` in
        jvp (and transpose, for the backward pass) or names it."""
        for path in parts:
            if outer not in path[:-1]:
                continue
            p = path[path.index(outer) + 1]
            inner = re.sub(r".*\(", "", p).rstrip(")")
            if inner == scope and ("transpose(" in p) == transpose:
                return True
        return False

    assert has("model", "grad") and has("model", "grad", transpose=True)
    assert has("unpack", "grad") == (backend == "pallas")
    assert any(p[:1] == ("opt",) and "adam" in p for p in parts)
    assert any(p[:1] == ("opt",) and "gossip" in p for p in parts)
    compress = [p for p in parts if "compress" in p]
    assert bool(compress) == (name == "cd-adam")
    assert all("gossip" in p[:p.index("compress")] for p in compress)


def test_nearly_every_top_level_op_is_scoped(compiled_step):
    """At least 90% of the fusions, kernels, copies and collectives that
    are not inside a fusion carry a layer scope, counting the ones that
    take their user's or caller's."""
    _, text = compiled_step
    comps, _ = parse_hlo(text)
    fused = {c for comp in comps.values() for i in comp.instrs
             if i.op == "fusion"
             for c in re.findall(r"calls=%([\w.\-]+)", i.attrs)}
    scopes = op_scopes(text)
    counted = [i.name for comp in comps.values() if comp.name not in fused
               for i in comp.instrs if i.op.startswith(COUNTED)]
    scoped = [n for n in counted if _names(scopes[n]) & SCOPES]
    assert len(counted) >= 10
    assert len(scoped) >= 0.9 * len(counted), (
        [(n, scopes[n]) for n in counted if n not in scoped])


def test_op_scopes_takes_a_user_or_caller_scope():
    text = """HloModule m

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
  %add.1 = f32[4]{0} add(%bitcast.1, %bitcast.1), metadata={op_name="jit(step)/grad/jvp(model)/add"}
  ROOT %gte = f32[4]{0} get-tuple-element(%c), index=0
}
"""
    s = op_scopes(text)
    assert s["a"] == "state[0]"
    # the nearest user with an op_name, breadth first: add.1 is two hops
    # from copy.1 (through the bitcast), the conditional three
    assert s["copy.1"] == s["bitcast.1"] == "jit(step)/grad/jvp(model)/add"
    assert s["tuple.1"] == "jit(step)/opt/cond"
    # the branch's copy has no user with one: the conditional's
    assert s["copy.2"] == s["tuple.2"] == "jit(step)/opt/cond"
    assert s["gte"] == ""


@pytest.mark.parametrize("hook", [False, True])
def test_fit_marks_each_step_and_phase_in_a_profile(hook, tmp_path):
    from jax.profiler import ProfileData

    tr, state = _trainer("d-adam", "reference", recompile_limit=4)
    feed = _batches()
    state, log = tr.fit(state, feed, 2)            # compiled outside
    kw = dict(hook=lambda step, s: None, hook_every=2) if hook else {}
    with jax.profiler.trace(str(tmp_path)):
        state, log = tr.fit(state, feed, 4, log_every=2, log=log, **kw)
    f = next(pathlib.Path(tmp_path).glob("**/*.xplane.pb"))
    events = [e for p in ProfileData.from_file(str(f)).planes
              if p.name.startswith("/host:CPU")
              for line in p.lines for e in line.events]
    count = {}
    for e in events:
        count[e.name] = count.get(e.name, 0) + 1
    assert {count.get(n) for n in ("fit.batch", "fit.watch",
                                   "fit.dispatch", "train")} == {4}
    assert count.get("fit.log") == 2
    assert count.get("fit.hook") == (2 if hook else None)
    steps = sorted(dict(e.stats)["step_num"] for e in events
                   if e.name == "train")
    assert steps == [3, 4, 5, 6]
