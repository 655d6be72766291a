"""The trip-count-aware HLO analyzer (roofline input correctness)."""
import warnings

import jax
import jax.numpy as jnp

from repro.analysis.hlo import (analyze, collective_bytes, full_cost,
                                unknown_dtypes_in)


def _compile(fn, *sds):
    return jax.jit(fn).lower(*sds).compile().as_text()


def test_scan_trip_count_multiplies_flops():
    """XLA's cost_analysis counts a while body once; ours multiplies by the
    trip count — pinned against the analytic matmul count."""
    def body(c, w):
        return jnp.tanh(c @ w), ()

    def fn(x, ws):
        return jax.lax.scan(body, x, ws)[0]

    x = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    ws = jax.ShapeDtypeStruct((10, 256, 256), jnp.float32)
    comp = jax.jit(fn).lower(x, ws).compile()
    ours = full_cost(comp.as_text())
    analytic = 2 * 128 * 256 * 256 * 10
    assert abs(ours["flops"] - analytic) / analytic < 0.05
    assert ours["unknown_trip_counts"] == 0
    # and XLA's raw number is ~10x short (the bug we correct)
    ca = comp.cost_analysis()
    if isinstance(ca, list):  # older jax returns a one-element list
        ca = ca[0]
    xla_flops = ca["flops"]
    assert xla_flops < analytic / 5


def test_nested_scan_multiplier():
    def inner(c, w):
        return c @ w, ()

    def outer(c, ws):
        c2, _ = jax.lax.scan(inner, c, ws)
        return c2, ()

    def fn(x, ws):
        return jax.lax.scan(lambda c, _: outer(c, ws), x, None, length=3)[0]

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    ws = jax.ShapeDtypeStruct((5, 64, 64), jnp.float32)
    txt = _compile(fn, x, ws)
    ours = full_cost(txt)
    analytic = 2 * 64 * 64 * 64 * 5 * 3
    assert abs(ours["flops"] - analytic) / analytic < 0.1


def test_dot_flops_exact():
    def fn(a, b):
        return a @ b

    a = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    b = jax.ShapeDtypeStruct((128, 32), jnp.float32)
    ours = full_cost(_compile(fn, a, b))
    assert abs(ours["flops"] - 2 * 64 * 128 * 32) / (2 * 64 * 128 * 32) < 0.05


def test_collective_parsing_synthetic_hlo():
    """Operand-byte semantics per collective kind on hand-written HLO."""
    hlo = """
HloModule test

ENTRY %main (p0: f32[128,8]) -> f32[128,8] {
  %p0 = f32[128,8]{1,0} parameter(0)
  %ar = f32[128,8]{1,0} all-reduce(%p0), replica_groups={{0,1,2,3}}, to_apply=%add
  %ag = f32[128,32]{1,0} all-gather(%ar), replica_groups=[2,4]<=[8], dimensions={1}
  %cp = f32[128,8]{1,0} collective-permute(%ar), source_target_pairs={{0,1},{1,0}}
  ROOT %out = f32[128,8]{1,0} add(%ar, %cp)
}
"""
    coll = collective_bytes(hlo)
    assert coll["all-reduce"] == 128 * 8 * 4
    # all-gather result / group_size(4) = operand
    assert coll["all-gather"] == 128 * 32 * 4 // 4
    assert coll["collective-permute"] == 128 * 8 * 4
    assert coll["total"] == sum(coll[k] for k in
                                ("all-reduce", "all-gather",
                                 "collective-permute", "all-to-all",
                                 "reduce-scatter"))


def test_variadic_collective_max_is_per_operand():
    """A combined (variadic) all-reduce adds every operand to the kind's
    bytes, but its largest single collective is its largest operand."""
    hlo = """
HloModule test

ENTRY %main (p0: f32[8,64], p1: f32[64]) -> (f32[8,64], f32[64]) {
  %p0 = f32[8,64]{1,0} parameter(0)
  %p1 = f32[64]{0} parameter(1)
  ROOT %ar = (f32[8,64]{1,0}, f32[64]{0}) all-reduce(%p0, %p1), replica_groups={{0,1}}, to_apply=%add
}
"""
    cost = analyze(hlo)
    assert cost.coll["all-reduce"] == (8 * 64 + 64) * 4
    assert cost.coll_counts["all-reduce"] == 1
    assert cost.coll_max["all-reduce"] == 8 * 64 * 4


def test_collectives_inside_while_multiplied():
    hlo = """
HloModule test

%cond (arg: (s32[], f32[64])) -> pred[] {
  %arg = (s32[], f32[64]{0}) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %t = s32[] constant(7)
  ROOT %lt = pred[] compare(%i, %t), direction=LT
}

%body (arg: (s32[], f32[64])) -> (s32[], f32[64]) {
  %arg = (s32[], f32[64]{0}) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %x = f32[64]{0} get-tuple-element(%arg), index=1
  %ar = f32[64]{0} all-reduce(%x), replica_groups={{0,1}}, to_apply=%add
  %one = s32[] constant(1)
  %i2 = s32[] add(%i, %one)
  ROOT %tup = (s32[], f32[64]) tuple(%i2, %ar)
}

ENTRY %main (p: (s32[], f32[64])) -> (s32[], f32[64]) {
  %p = (s32[], f32[64]{0}) parameter(0)
  ROOT %w = (s32[], f32[64]) while(%p), condition=%cond, body=%body
}
"""
    coll = collective_bytes(hlo)
    assert coll["all-reduce"] == 7 * 64 * 4


def test_real_sharded_program_collectives(tmp_path):
    """Rolls over a sharded leading dim lower to collective-permutes whose
    bytes the analyzer attributes (run on whatever host devices exist —
    single-device programs simply have zero collective bytes)."""
    def fn(x):
        return x / 3 + jnp.roll(x, 1, 0) / 3 + jnp.roll(x, -1, 0) / 3

    x = jax.ShapeDtypeStruct((4, 64), jnp.float32)
    txt = _compile(fn, x)
    coll = collective_bytes(txt)
    assert coll["total"] >= 0  # parses without error


# ------------------------- dtype-table coverage ------------------------------


def test_unknown_dtype_counted_not_dropped():
    """A dtype outside the table contributes a conservative 4 bytes/elem
    (and warns once) instead of silently zeroing the byte accounting."""
    hlo = """
HloModule test

ENTRY %main (p0: f9z[16,8]) -> f9z[16,8] {
  %p0 = f9z[16,8]{1,0} parameter(0)
  ROOT %ar = f9z[16,8]{1,0} all-reduce(%p0), replica_groups={{0,1}}, to_apply=%add
}
"""
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        coll = collective_bytes(hlo)
    assert coll["all-reduce"] == 16 * 8 * 4  # conservative fallback, not 0
    assert any("f9z" in str(w.message) for w in caught)

    # every textual shape occurrence counts: 2 in the ENTRY signature +
    # the parameter and all-reduce defs
    cost = analyze(hlo)
    assert cost.unknown_dtypes == {"f9z": 4 * 16 * 8}
    assert full_cost(hlo)["unknown_dtype_elems"] == 4 * 16 * 8
    assert unknown_dtypes_in(hlo) == {"f9z": 4 * 16 * 8}


def test_known_exotic_dtypes_in_table():
    """The narrow-float / sub-byte additions carry their real widths."""
    hlo = """
HloModule test

ENTRY %main (p0: f8e4m3[32]) -> bf16[32] {
  %p0 = f8e4m3[32]{0} parameter(0)
  %a = f8e4m3[32]{0} all-reduce(%p0), replica_groups={{0,1}}, to_apply=%add
  ROOT %c = bf16[32]{0} convert(%a)
}
"""
    assert not unknown_dtypes_in(hlo)
    assert collective_bytes(hlo)["all-reduce"] == 32 * 1  # 1 byte/elem


def test_metadata_brackets_not_parsed_as_dtypes():
    """Identifiers like pending[4] / bufs[1] inside op metadata must not
    register as unknown dtypes (the INV005 false-positive class)."""
    hlo = """
HloModule test

ENTRY %main (p0: f32[4]) -> f32[4] {
  %p0 = f32[4]{0} parameter(0), metadata={op_name="jit(f)/pending[4]/bufs[1]"}
  ROOT %n = f32[4]{0} negate(%p0)
}
"""
    assert unknown_dtypes_in(hlo) == {}


def test_max_trip_count_tracked():
    hlo = """
HloModule test

%cond (arg: (s32[], f32[64])) -> pred[] {
  %arg = (s32[], f32[64]{0}) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %t = s32[] constant(9)
  ROOT %lt = pred[] compare(%i, %t), direction=LT
}

%body (arg: (s32[], f32[64])) -> (s32[], f32[64]) {
  %arg = (s32[], f32[64]{0}) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %x = f32[64]{0} get-tuple-element(%arg), index=1
  %one = s32[] constant(1)
  %i2 = s32[] add(%i, %one)
  ROOT %tup = (s32[], f32[64]) tuple(%i2, %x)
}

ENTRY %main (p: (s32[], f32[64])) -> (s32[], f32[64]) {
  %p = (s32[], f32[64]{0}) parameter(0)
  ROOT %w = (s32[], f32[64]) while(%p), condition=%cond, body=%body
}
"""
    cost = analyze(hlo)
    assert cost.max_trip_count == 9
    assert full_cost(hlo)["max_trip_count"] == 9
