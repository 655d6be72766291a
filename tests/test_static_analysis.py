"""The three-pass shard-safety static analyzer (PR-7 tentpole).

Pins, through the same entry points CI uses
(``repro.analysis.check`` / ``scripts/check_invariants.py``):

* the **known-bug corpus** — the PR-5 raw-psum sharded loss trips JXL001
  (forward custom_vjp walk AND backward psum accounting) and RPR001; the
  PR-6 flat-circulant torus fails INV006 through ``check_topology``;
* the **invariant spec mechanics** on synthetic HLO (count/byte/single/
  trip bounds, min counts, "*" totals, InvariantViolation);
* the **jaxpr lint** on hand-built shard_map programs (raw vs protected
  collectives, wrong-axis binding);
* the **AST rules** RPR001–RPR004 including ``# noqa`` suppression, and
  that the shipped ``src/`` tree is clean;
* the **RecompileWatch** (JXL003) both standalone and wired into the
  trainer via ``recompile_limit=``.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import astlint
from repro.analysis.invariants import (InvariantSpec, InvariantViolation,
                                       assert_invariants, assert_topology,
                                       check_topology, evaluate_hlo)
from repro.analysis.jaxpr_lint import (RecompileError, RecompileWatch,
                                       lint_fn)


def skip_unless_devices(n):
    if jax.device_count() < n:
        pytest.skip(f"needs >= {n} devices, have {jax.device_count()}")


# --------------------------- invariant mechanics -----------------------------


_SYNTH_HLO = """
HloModule test

ENTRY %main (p0: f32[128,8]) -> f32[128,8] {
  %p0 = f32[128,8]{1,0} parameter(0)
  %ar = f32[128,8]{1,0} all-reduce(%p0), replica_groups={{0,1,2,3}}, to_apply=%add
  %cp = f32[128,8]{1,0} collective-permute(%ar), source_target_pairs={{0,1},{1,0}}
  ROOT %out = f32[128,8]{1,0} add(%ar, %cp)
}
"""
_OP_BYTES = 128 * 8 * 4  # one f32[128,8] operand


class TestInvariantSpec:
    def test_pass(self):
        spec = InvariantSpec(
            collective_counts={"all-gather": 0, "all-reduce": 1},
            min_collective_counts={"collective-permute": 1},
            collective_bytes={"*": 2 * _OP_BYTES},
            single_collective_bytes={"all-reduce": _OP_BYTES})
        report = evaluate_hlo(_SYNTH_HLO, spec)
        assert report.ok, report.format()
        # informational summary always populated, all five kinds
        assert set(report.summary) == {
            "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
            "collective-permute"}
        assert report.summary["all-reduce"]["count"] == 1

    @pytest.mark.parametrize("spec,rule", [
        (InvariantSpec(collective_counts={"all-reduce": 0}), "INV001"),
        (InvariantSpec(min_collective_counts={"all-gather": 1}), "INV001"),
        (InvariantSpec(collective_bytes={"*": _OP_BYTES}), "INV002"),
        (InvariantSpec(collective_bytes={"all-reduce": _OP_BYTES - 1}),
         "INV002"),
        (InvariantSpec(single_collective_bytes={
            "collective-permute": _OP_BYTES - 1}), "INV003"),
    ])
    def test_each_bound_fails_with_its_rule(self, spec, rule):
        report = evaluate_hlo(_SYNTH_HLO, spec)
        assert not report.ok
        assert report.failed_rules() == [rule]

    def test_assert_invariants_raises_with_report(self):
        def fn(x):
            return x * 2

        x = jnp.ones((8, 8))
        # impossible bound: demand a collective a single-device program
        # cannot have
        spec = InvariantSpec(min_collective_counts={"all-gather": 1})
        with pytest.raises(InvariantViolation) as ei:
            assert_invariants(fn, (x,), spec)
        assert "INV001" in str(ei.value)
        assert ei.value.report.failed_rules() == ["INV001"]
        # and a satisfiable spec returns the report
        report = assert_invariants(fn, (x,), InvariantSpec(
            collective_counts={"all-gather": 0}))
        assert report.ok


# --------------------------- topology invariants -----------------------------


class TestTopologyInvariants:
    def test_zoo_clean(self):
        from repro.analysis.check import topology_reports
        for report in topology_reports():
            assert report.ok, report.format()

    def test_corpus_bad_torus_fails_inv006(self):
        """PR-6 bug class: flat circulant offsets on a 2x4 torus wrap the
        ±1 hops across row boundaries — the lowered permutation matrix
        cannot equal the dense weights."""
        from repro.analysis.check import corpus_bad_torus
        report = corpus_bad_torus()
        assert not report.ok
        assert "INV006" in report.failed_rules()
        with pytest.raises(InvariantViolation):
            from repro.core.topology import make_topology
            bad = dataclasses.replace(
                make_topology("torus", 8), name="bad-flat-torus",
                offsets=(1, -1, 4, -4))
            assert_topology(bad)

    def test_good_torus_passes(self):
        from repro.core.topology import make_topology
        assert check_topology(make_topology("torus", 8)).ok

    def test_non_doubly_stochastic_fails_inv007(self):
        from repro.core.topology import make_topology
        import numpy as np
        ring = make_topology("ring", 4)
        W = np.asarray(ring.weights).copy()
        W[0, 0] += 0.25
        bad = dataclasses.replace(ring, weights=W)
        report = check_topology(bad)
        assert "INV007" in report.failed_rules()


# ------------------------------- jaxpr lint ----------------------------------


class TestJaxprLint:
    def _shard_mapped(self, body):
        from jax.sharding import Mesh, PartitionSpec as P
        import numpy as np
        mesh = Mesh(np.array(jax.devices()[:1]), ("worker",))
        return jax.shard_map(body, mesh=mesh, in_specs=P(),
                             out_specs=P(), check_vma=False)

    def test_raw_psum_flagged(self):
        fn = self._shard_mapped(lambda x: jax.lax.psum(x, "worker"))
        findings = lint_fn(fn, jnp.ones(4),
                           gossip_axes=(), reduce_axes=("worker",))
        assert [f.rule for f in findings] == ["JXL001"]

    def test_protected_psum_clean(self):
        from repro.train.grad import psum_replicated
        fn = self._shard_mapped(lambda x: psum_replicated(x, "worker"))
        findings = lint_fn(fn, jnp.ones(4),
                           gossip_axes=(), reduce_axes=("worker",))
        assert findings == []

    def test_wrong_axis_reduce_flagged(self):
        # a psum over the GOSSIP axis is a wrong-axis reduction (JXL002);
        # check_raw off isolates the axis rule
        fn = self._shard_mapped(lambda x: jax.lax.psum(x, "worker"))
        findings = lint_fn(fn, jnp.ones(4), check_raw=False,
                           gossip_axes=("worker",), reduce_axes=("model",))
        assert [f.rule for f in findings] == ["JXL002"]

    def test_gossip_permute_on_gossip_axis_clean(self):
        fn = self._shard_mapped(
            lambda x: jax.lax.ppermute(x, "worker", [(0, 0)]))
        findings = lint_fn(fn, jnp.ones(4), check_raw=False,
                           gossip_axes=("worker",), reduce_axes=("model",))
        assert findings == []


class TestRawPsumCorpus:
    def test_corpus_raw_psum_trips_jxl001_both_modes(self):
        """The PR-5 bug class through the real pipeline: the forward
        custom_vjp-boundary walk AND the backward psum-shape accounting
        must both flag the raw-psum sharded loss."""
        skip_unless_devices(8)
        from repro.analysis.check import corpus_raw_psum
        rules = [f.rule for f in corpus_raw_psum()]
        assert rules.count("JXL001") >= 2

    def test_safe_pipeline_clean(self):
        skip_unless_devices(8)
        from repro.analysis.check import SweepConfig, check_config
        res = check_config(SweepConfig("axis2d", "d-adam", "plain", M=2))
        assert res.skipped is None
        assert res.lint == []
        assert res.report.ok, res.report.format()


# -------------------------------- AST rules ----------------------------------


class TestAstRules:
    def test_corpus_trips_all_rules(self):
        from repro.analysis.check import corpus_ast
        counts = astlint.rule_counts(corpus_ast())
        for rule in ("RPR001", "RPR002", "RPR003", "RPR004"):
            assert counts[rule] >= 1, (rule, counts)

    def test_noqa_suppression(self):
        src = ("import jax\n"
               "def f(chunks, batch, ctx):\n"
               "    return jax.lax.psum(chunks, ctx.axis_name)"
               "  # noqa: RPR001\n")
        assert astlint.lint_source(src) == []
        # a noqa for a different rule does not suppress
        src_wrong = src.replace("RPR001", "RPR002")
        assert [f.rule for f in astlint.lint_source(src_wrong)] == ["RPR001"]

    def test_ctx_psum_not_flagged(self):
        src = ("def f(chunks, batch, ctx):\n"
               "    return ctx.psum(chunks.sum())\n")
        assert astlint.lint_source(src) == []

    def test_pallas_interpret_kwarg_ok(self):
        src = ("from jax.experimental import pallas as pl\n"
               "def k(x, interp):\n"
               "    return pl.pallas_call(lambda r, o: None, out_shape=x,"
               " interpret=interp)(x)\n")
        assert astlint.lint_source(src) == []

    def test_static_blockspec_ok(self):
        src = ("from jax.experimental import pallas as pl\n"
               "def s(K):\n"
               "    return pl.BlockSpec((1, 8, 128),"
               " lambda k, i: (k // 2, i, 0))\n")
        assert astlint.lint_source(src) == []

    def test_src_tree_clean(self):
        """The shipped source must stay lint-clean — the same gate the CI
        static-analysis job enforces."""
        import pathlib
        src_root = pathlib.Path(__file__).resolve().parents[1] / "src"
        findings = astlint.lint_paths([str(src_root)])
        assert findings == [], "\n".join(str(f) for f in findings)

    def test_cli_exit_codes(self, tmp_path):
        clean = tmp_path / "clean.py"
        clean.write_text("x = 1\n")
        assert astlint.main([str(clean)]) == 0
        dirty = tmp_path / "dirty.py"
        dirty.write_text(
            "import jax\n"
            "def bad_sharded_loss(c, b, ctx):\n"
            "    return jax.lax.psum(c, ctx.axis_name)\n")
        assert astlint.main([str(dirty), "--summary"]) == 1


# --------------------------- JXL003: recompiles ------------------------------


class TestRecompileWatch:
    def test_limit_and_reset(self):
        w = RecompileWatch("f", limit=1)
        assert w.observe(jnp.ones((4,))) == 1
        assert w.observe(jnp.ones((4,))) == 1      # same signature
        w.check()                                   # within limit
        assert w.observe(jnp.ones((5,))) == 2       # shape churn
        assert [f.rule for f in w.findings()] == ["JXL003"]
        with pytest.raises(RecompileError):
            w.check()
        w.reset()
        assert w.findings() == []

    def test_dtype_and_structure_churn_counts(self):
        w = RecompileWatch(limit=1)
        w.observe({"a": jnp.ones((2,), jnp.float32)})
        w.observe({"a": jnp.ones((2,), jnp.int32)})
        w.observe({"a": jnp.ones((2,)), "b": jnp.ones((2,))})
        assert len(w.signatures) == 3

    def test_trainer_recompile_limit(self):
        """recompile_limit= wires the watch into fit(): a batch-shape
        change mid-run raises instead of silently recompiling."""
        from repro.core import make_optimizer
        from repro.train import DecentralizedTrainer

        def loss(p, batch):
            return jnp.mean((batch @ p["w"]) ** 2)

        K = 2
        opt = make_optimizer("d-adam", K=K, eta=1e-2, period=2)
        tr = DecentralizedTrainer(loss, opt, recompile_limit=1)
        assert tr.recompile_watch is not None
        state = tr.init({"w": jnp.ones((4, 2))})

        def batches(shapes):
            for s in shapes:
                yield jnp.ones((K,) + s)

        state, _ = tr.fit(state, batches([(3, 4)] * 4), 4, log_every=2)
        with pytest.raises(RecompileError):
            tr.fit(state, batches([(3, 4), (5, 4)]), 2, log_every=1)

    def test_trainer_default_no_watch(self):
        from repro.core import make_optimizer
        from repro.train import DecentralizedTrainer
        opt = make_optimizer("d-adam", K=2, eta=1e-2, period=2)
        tr = DecentralizedTrainer(lambda p, b: jnp.mean(p["w"] * b), opt)
        assert tr.recompile_watch is None


# ------------------------------ sweep surface --------------------------------


class TestSweep:
    def test_sweep_config_shape(self):
        from repro.analysis.check import sweep_configs
        cfgs = sweep_configs()
        names = {c.name for c in cfgs}
        # invalid combos excluded by construction
        assert "axis2d/d-adam/stale" not in names
        assert "axis/cd-adam/stale" not in names
        assert "reference/d-adam/plain" in names
        assert all(c.M == (2 if c.backend == "axis2d" else 1) for c in cfgs)

    def test_stacked_config_passes(self):
        from repro.analysis.check import SweepConfig, check_config
        res = check_config(SweepConfig("reference", "d-adam", "plain"))
        assert res.ok, (res.report and res.report.format(), res.lint)
