"""Jaxpr-level shard-safety lint (rule namespace ``JXL``).

The PR-5 bug class: a raw ``lax.psum`` inside a ``shard_map(...,
check_vma=False)`` region transposes to *another* psum applied to an
already-replicated cotangent, silently scaling every gradient by the
mesh-axis size. The safe patterns (``train.grad.psum_replicated`` /
``_slice_replicated``) route the collective through a ``custom_vjp`` whose
backward rule is shaped by hand. This module makes the distinction
checkable:

``JXL001``  raw ``psum`` / ``all_gather`` inside a ``check_vma=False``
            shard_map region that is not under a ``custom_vjp`` boundary.
            Two detection modes, because AD *inlines* custom_vjp bodies
            (a grad trace of a protected and a raw loss are structurally
            indistinguishable):

            * forward — :func:`lint_jaxpr` on a *pre-AD* trace, where
              ``custom_vjp_call_jaxpr`` equations are still visible;
            * backward — :func:`lint_grad_psums` compares the psum count
              of the grad trace against what the forward trace predicts
              (every forward psum replays, plus exactly one transpose
              psum per slice-like custom_vjp). A surplus psum is a raw
              collective's transpose.

``JXL002``  collective bound to the wrong mesh axis: a ``ppermute``
            (neighbor gossip) over a reduce axis, or a ``psum`` /
            ``all_gather`` (reduction) over a gossip axis.

``JXL003``  recompilation: :class:`RecompileWatch` hashes abstract call
            signatures (tree structure + leaf shape/dtype) and flags when
            distinct signatures exceed a limit.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence, Tuple

import jax
from jax.extend import core as jax_core

RULES = {
    "JXL001": ("raw collective under shard_map(check_vma=False) outside a "
               "custom_vjp boundary (gradient-scaling bug class)"),
    "JXL002": "collective bound to the wrong mesh axis",
    "JXL003": "abstract call signature churn (recompilation)",
}

# primitives whose transpose under check_vma=False replicated cotangents
# produces the M-times gradient scaling
_RAW_COLLECTIVES = ("psum", "all_gather")
# reduction-flavored vs neighbor-shift-flavored collectives for JXL002
_REDUCE_PRIMS = ("psum", "pmax", "pmin", "all_gather", "all_to_all")
_SHIFT_PRIMS = ("ppermute", "pshuffle")


@dataclasses.dataclass(frozen=True)
class Finding:
    rule: str
    message: str
    path: Tuple[str, ...] = ()

    def __str__(self) -> str:
        where = " > ".join(self.path) if self.path else "<top>"
        return f"{self.rule} [{where}]: {self.message}"


@dataclasses.dataclass(frozen=True)
class _Ctx:
    in_norep_shardmap: bool = False
    protected: bool = False
    path: Tuple[str, ...] = ()


def _as_jaxpr(obj: Any) -> Optional[jax_core.Jaxpr]:
    if isinstance(obj, jax_core.ClosedJaxpr):
        return obj.jaxpr
    if isinstance(obj, jax_core.Jaxpr):
        return obj
    return None


def _sub_jaxprs(params: Dict[str, Any]) -> Iterable[Tuple[str, jax_core.Jaxpr]]:
    """Every Jaxpr reachable from an equation's params, generically —
    sub-jaxprs hide under many param names (jaxpr, call_jaxpr, fun_jaxpr,
    branches, ...) and sometimes inside tuples."""
    for key, val in params.items():
        vals = val if isinstance(val, (tuple, list)) else (val,)
        for v in vals:
            j = _as_jaxpr(v)
            if j is not None:
                yield key, j


def _axis_names(params: Dict[str, Any]) -> Tuple[str, ...]:
    names: List[str] = []
    for key in ("axes", "axis_name", "axis_index_groups_axis"):
        v = params.get(key)
        if v is None:
            continue
        for n in v if isinstance(v, (tuple, list)) else (v,):
            if isinstance(n, str):
                names.append(n)
    return tuple(names)


def _eqn_is_norep_shardmap(eqn) -> bool:
    return (eqn.primitive.name == "shard_map"
            and eqn.params.get("check_vma") is False)


def _eqn_is_custom_vjp(eqn) -> bool:
    return eqn.primitive.name.startswith("custom_vjp_call")


def lint_jaxpr(jaxpr: Any, *,
               gossip_axes: Sequence[str] = ("worker",),
               reduce_axes: Sequence[str] = ("model",),
               check_raw: bool = True,
               check_axes: bool = True) -> List[Finding]:
    """Walk a (closed) jaxpr and report JXL001/JXL002 findings.

    ``check_raw`` must only be enabled on traces of *differentiated* code
    (a loss / grad pipeline): a raw psum in non-AD code (e.g. a compressor
    psum-ing scale factors inside the optimizer step) is legitimate.
    Wrong-axis checks apply everywhere.
    """
    findings: List[Finding] = []
    root = _as_jaxpr(jaxpr)
    if root is None:
        raise TypeError(f"expected a Jaxpr/ClosedJaxpr, got {type(jaxpr)!r}")

    def walk(j: jax_core.Jaxpr, ctx: _Ctx) -> None:
        for eqn in j.eqns:
            name = eqn.primitive.name
            axes = _axis_names(eqn.params)
            if ctx.in_norep_shardmap:
                if (check_raw and name in _RAW_COLLECTIVES
                        and not ctx.protected):
                    findings.append(Finding(
                        "JXL001",
                        f"raw `{name}` over {axes or '<?>'} inside "
                        "shard_map(check_vma=False); route it through "
                        "psum_replicated / a custom_vjp or its transpose "
                        "will scale gradients by the axis size",
                        ctx.path))
                if check_axes:
                    bad_shift = (name in _SHIFT_PRIMS
                                 and any(a in reduce_axes for a in axes))
                    bad_reduce = (name in _REDUCE_PRIMS
                                  and any(a in gossip_axes for a in axes))
                    if bad_shift or bad_reduce:
                        role = "gossip" if bad_shift else "reduction"
                        findings.append(Finding(
                            "JXL002",
                            f"`{name}` ({role} collective) bound to mesh "
                            f"axes {axes}; gossip belongs on "
                            f"{tuple(gossip_axes)}, reductions on "
                            f"{tuple(reduce_axes)}",
                            ctx.path))
            sub_ctx = _Ctx(
                in_norep_shardmap=(ctx.in_norep_shardmap
                                   or _eqn_is_norep_shardmap(eqn)),
                protected=ctx.protected or _eqn_is_custom_vjp(eqn),
                path=ctx.path + (name,))
            for _, sub in _sub_jaxprs(eqn.params):
                walk(sub, sub_ctx)

    walk(root, _Ctx())
    return findings


def lint_fn(fn: Callable, *args: Any, **lint_kwargs: Any) -> List[Finding]:
    """Trace ``fn(*args)`` (pre-AD) and lint the jaxpr."""
    return lint_jaxpr(jax.make_jaxpr(fn)(*args), **lint_kwargs)


def _psum_accounting(jaxpr: Any) -> Tuple[Dict[Tuple, int], Dict[Tuple, int]]:
    """Shape-multiset accounting of psums inside check_vma=False regions:

    returns ``(psum_shapes, slice_input_shapes)`` — output-shape -> count
    for every psum, and input-shape -> count for every *slice-like*
    custom_vjp (forward body contains a ``dynamic_slice``; its hand-written
    backward contributes at most one psum of the FULL input shape — see
    train.grad._slice_replicated)."""
    psums: Dict[Tuple, int] = {}
    slices: Dict[Tuple, int] = {}
    root = _as_jaxpr(jaxpr)

    def has_dynamic_slice(j: jax_core.Jaxpr) -> bool:
        for eqn in j.eqns:
            if eqn.primitive.name == "dynamic_slice":
                return True
            for _, sub in _sub_jaxprs(eqn.params):
                if has_dynamic_slice(sub):
                    return True
        return False

    def walk(j: jax_core.Jaxpr, norep: bool) -> None:
        for eqn in j.eqns:
            if norep and eqn.primitive.name == "psum":
                for v in eqn.outvars:
                    s = tuple(getattr(v.aval, "shape", ()))
                    psums[s] = psums.get(s, 0) + 1
            if norep and _eqn_is_custom_vjp(eqn):
                if any(has_dynamic_slice(sub)
                       for _, sub in _sub_jaxprs(eqn.params)):
                    for v in eqn.invars:
                        s = tuple(getattr(v.aval, "shape", ()))
                        slices[s] = slices.get(s, 0) + 1
                        break
            sub_norep = norep or _eqn_is_norep_shardmap(eqn)
            for _, sub in _sub_jaxprs(eqn.params):
                walk(sub, sub_norep)

    walk(root, False)
    return psums, slices


def lint_grad_psums(forward_fn: Callable, grad_fn: Callable,
                    args: Sequence[Any]) -> List[Finding]:
    """JXL001 on the *backward* jaxpr, by psum shape accounting.

    ``forward_fn`` is a pre-AD forward-only twin of ``grad_fn`` (same
    shard_map structure, no differentiation — see
    ``train.grad.sharded_loss_probe``). In the grad trace every legitimate
    psum is either a replay of a forward psum (same output shape) or the
    transpose of a slice-like custom_vjp (a psum of the slice's FULL input
    shape, which AD may also dead-code away when the sliced operand does
    not depend on params). A *raw* forward psum transposes into one extra
    psum of its own output shape — so for some shape the grad count
    exceeds forward-count + slice-count, and that surplus flags the bug
    class even though AD has erased the custom_vjp boundaries.
    """
    fwd = jax.make_jaxpr(forward_fn)(*args)
    grad = jax.make_jaxpr(grad_fn)(*args)
    f_psums, f_slices = _psum_accounting(fwd)
    g_psums, _ = _psum_accounting(grad)
    findings: List[Finding] = []
    for shape, g in sorted(g_psums.items()):
        allowed = f_psums.get(shape, 0) + f_slices.get(shape, 0)
        if g > allowed:
            findings.append(Finding(
                "JXL001",
                f"grad trace has {g} psum(s) of shape {shape} inside "
                f"check_vma=False regions but the forward trace only "
                f"accounts for {allowed} (forward replays + slice "
                "transposes); the surplus is a raw collective's transpose "
                "replicating cotangents (gradient-scaling bug)"))
    return findings


# ---------------------------- JXL003: recompiles -----------------------------


def _abstract_signature(args: Tuple[Any, ...], kwargs: Dict[str, Any]):
    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))

    def leaf_sig(x: Any):
        shape = getattr(x, "shape", None)
        dtype = getattr(x, "dtype", None)
        if shape is not None and dtype is not None:
            return (tuple(shape), str(dtype))
        # python scalars etc. retrigger tracing by value type
        return (type(x).__name__,)

    return (str(treedef), tuple(leaf_sig(x) for x in leaves))


class RecompileWatch:
    """Hash abstract call signatures across trainer calls; more than
    ``limit`` distinct signatures means jit is recompiling (JXL003).

    ``limit`` defaults to 1: one signature per build. Elastic resize is a
    *legitimate* recompile — reset the watch (or build a fresh one) at
    rebuild points rather than raising the limit.
    """

    def __init__(self, name: str = "fn", limit: int = 1):
        self.name = name
        self.limit = int(limit)
        self.signatures: Dict[Any, int] = {}

    def reset(self) -> None:
        self.signatures.clear()

    def observe(self, *args: Any, **kwargs: Any) -> int:
        """Record one call; returns the number of distinct signatures."""
        sig = _abstract_signature(args, kwargs)
        self.signatures[sig] = self.signatures.get(sig, 0) + 1
        return len(self.signatures)

    def findings(self) -> List[Finding]:
        n = len(self.signatures)
        if n > self.limit:
            return [Finding(
                "JXL003",
                f"`{self.name}` saw {n} distinct abstract signatures "
                f"(limit {self.limit}): each one is a fresh XLA compile. "
                "Pin shapes/dtypes (pad batches, static microbatch "
                "counts) or reset the watch at legitimate rebuild points")]
        return []

    def check(self) -> None:
        f = self.findings()
        if f:
            raise RecompileError(str(f[0]))


class RecompileError(RuntimeError):
    pass
