"""Pytree <-> lane-aligned buffer packing for the Pallas optimizer kernels.

The fused-Adam / sign-compress kernels operate on (rows, 128) VMEM-tileable
buffers; optimizer state lives as ragged parameter pytrees. This module is
the bridge: a ``PackSpec`` captures the leaf layout of a tree once, and
``pack`` / ``unpack`` move congruent trees in and out of a single flat
buffer.

Three layouts:

* **flat** (``make_spec(tree)``): every element of every leaf — including a
  stacked worker dim — is concatenated into one (rows, LANE) buffer, so the
  whole parameter vector is ONE kernel launch. This is what the fused-Adam
  dispatch uses: the update is elementwise, so worker/leaf boundaries don't
  affect the math.
* **stacked** (``make_spec(tree, stacked=True)``): the leading worker dim K
  is preserved; per-worker contents are concatenated and padded to a
  (K, rows, LANE) buffer whose row k holds exactly worker k's elements.
* **stacked + leaf-aligned** (``make_spec(tree, stacked=True,
  leaf_align=True)``): additionally every leaf segment is padded up to
  whole (block_rows, LANE) tiles, so each leaf occupies a contiguous,
  tile-aligned row range of the buffer (``leaf_row_ranges``). This is the
  *resident* layout of the packed optimizer states: per-(worker, leaf)
  kernels — e.g. CD-Adam's sign compression, whose reference semantics put
  one scale per (worker, leaf) — run directly on buffer *slices*, with no
  per-step pack/unpack and no coarsening of the per-leaf math.
* **row-sharded** (``make_spec(..., leaf_align=True, row_shards=M)``): the
  2D (worker × model) mesh layout. Every leaf segment is padded to a whole
  multiple of ``M`` tiles and *split round-robin across M equal row
  shards*: the buffer's row dim is organized as M contiguous shard blocks,
  and shard block j holds the j-th 1/M chunk of EVERY leaf, in leaf order.
  Sharding the row dim over a 'model' mesh axis with ``PartitionSpec
  ('worker', 'model')`` therefore gives each device 1/M of every leaf at
  *static, shard-invariant* local row ranges — ``leaf_row_ranges`` returns
  those per-shard local ranges, so the per-(worker, leaf) kernels run
  unchanged on each model shard (the scale reduction psums over the model
  axis; see ``sign_compress_stacked(reduce_axis=...)``).

Padding is to whole (block_rows, LANE) tiles so the kernels never re-pad,
and is zero-filled — the optimizer kernels preserve zeros in padding, so a
resident buffer's padding stays zero across arbitrarily many steps.
Mixed-dtype trees are packed in the widest participating float dtype
(``jnp.result_type``) and cast back per leaf on unpack, which is lossless
for the bf16-in-f32 case; the pack/unpack pair is an exact inverse.
Integer-dtype leaves are rejected outright: packing them through the float
buffer would silently corrupt them in the kernels' ``sqrt``/``sign`` math.

All sizes in the spec are Python ints — specs are hashable static data,
safe to close over in jitted functions and to carry as static aux_data of
a registered pytree (how the packed optimizer states hold them).
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any

LANE = 128
# Shared VMEM tile quantum: (BLOCK_ROWS, LANE) f32 = 128 KiB/operand. The
# resident packed layout aligns to it so fused_adam / gossip /
# sign_compress (which import it from here) never re-pad a buffer.
BLOCK_ROWS = 256


class PackSpec(NamedTuple):
    treedef: Any
    shapes: Tuple[Tuple[int, ...], ...]   # full leaf shapes (incl. K if stacked)
    dtypes: Tuple[Any, ...]
    sizes: Tuple[int, ...]                # per-(worker-)leaf element counts
    offsets: Tuple[int, ...]              # per-leaf start offset in the
    #                                       padded flat (per-worker) buffer;
    #                                       PER-SHARD offsets when row_shards>1
    n: int                                # true elements per worker (sum sizes)
    rows: int                             # padded row count: rows*LANE >= n
    k: Optional[int]                      # worker count; None in flat mode
    row_shards: int = 1                   # model-axis row shards (2D layout)

    @property
    def stacked(self) -> bool:
        return self.k is not None

    @property
    def padded(self) -> int:
        return self.rows * LANE

    @property
    def local_rows(self) -> int:
        """Rows of one model shard (== ``rows`` when not row-sharded)."""
        return self.rows // self.row_shards

    @property
    def leaf_aligned(self) -> bool:
        """True when every leaf segment starts on a LANE boundary (the
        leaf_align layout), i.e. per-leaf buffer slices are row ranges."""
        return all(o % LANE == 0 for o in self.offsets) and \
            self.padded % LANE == 0

    def buf_shape(self) -> Tuple[int, ...]:
        return ((self.k, self.rows, LANE) if self.stacked
                else (self.rows, LANE))


def is_packed_buffer_shape(shape, k: Optional[int] = None) -> bool:
    """True when ``shape`` is a stacked packed-buffer shape
    ``(K, rows, LANE)`` — THE shared recognition rule the 2D sharding
    helpers use to decide which leaves of a state/grads tree get their
    row dim placed on a 'model' mesh axis (everything else — scalars,
    batch stacks, reference pytree leaves — replicates over it)."""
    return (len(shape) == 3 and shape[-1] == LANE
            and (k is None or shape[0] == k))


def _require_float(dtypes, what: str) -> None:
    for dt in dtypes:
        if not jnp.issubdtype(dt, jnp.floating):
            raise ValueError(
                f"{what} requires float leaves; got dtype {dt} — packing "
                "integer data through the float buffer would corrupt it in "
                "the kernels' sqrt/sign math (cast it explicitly first, or "
                "keep it out of the packed tree)")


def make_spec(tree: PyTree, *, stacked: bool = False,
              block_rows: int = 1, leaf_align: bool = False,
              row_shards: int = 1) -> PackSpec:
    """Record the layout of ``tree``; pad up to whole (block_rows, LANE)
    tiles. With ``leaf_align`` every *leaf segment* is padded to whole
    tiles, so each leaf occupies a contiguous tile-aligned row range. With
    ``row_shards=M`` (requires stacked + leaf_align) every segment is
    additionally padded to a multiple of M tiles and split across M equal
    row-shard blocks — the 2D (worker × model) mesh layout. Any tree
    congruent with ``tree`` (same treedef + leaf shapes) can then be
    packed against this spec, regardless of (float) leaf dtypes."""
    if row_shards < 1:
        raise ValueError(f"row_shards must be >= 1, got {row_shards}")
    if row_shards > 1 and not (stacked and leaf_align):
        raise ValueError(
            "row_shards > 1 needs stacked=True and leaf_align=True (the "
            "row-sharded layout is defined over leaf-aligned shard blocks)")
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if not leaves:
        raise ValueError("cannot pack an empty pytree")
    shapes = tuple(tuple(l.shape) for l in leaves)
    dtypes = tuple(jnp.dtype(l.dtype) for l in leaves)
    _require_float(dtypes, "pack()")
    k: Optional[int] = None
    if stacked:
        ks = {s[0] if s else None for s in shapes}
        if len(ks) != 1 or None in ks:
            raise ValueError(
                f"stacked pack needs a shared leading worker dim; got {shapes}")
        (k,) = ks
        sizes = tuple(int(np.prod(s[1:], dtype=np.int64)) for s in shapes)
    else:
        sizes = tuple(int(np.prod(s, dtype=np.int64)) for s in shapes)
    per_tile = block_rows * LANE
    if leaf_align:
        quantum = per_tile * row_shards
        seg = tuple(sz + (-sz) % quantum for sz in sizes)
        # offsets are within ONE shard block (the whole buffer when
        # row_shards == 1): cumulative per-shard chunk starts
        chunks = tuple(s // row_shards for s in seg)
        offsets = tuple(int(o) for o in np.cumsum((0,) + chunks)[:-1])
        padded = int(sum(seg))
    else:
        offsets = tuple(int(o) for o in np.cumsum((0,) + sizes)[:-1])
        n_true = sum(sizes)
        padded = n_true + (-n_true) % per_tile
    return PackSpec(treedef=treedef, shapes=shapes, dtypes=dtypes,
                    sizes=sizes, offsets=offsets, n=sum(sizes),
                    rows=padded // LANE, k=k, row_shards=row_shards)


def leaf_row_ranges(spec: PackSpec) -> Tuple[Tuple[int, int], ...]:
    """Per-leaf (row_start, row_end) within the buffer. Requires the
    leaf-aligned layout (each segment a whole number of rows).

    For a row-sharded spec (``row_shards=M``) the ranges are *local to one
    shard block* — identical on every shard, which is exactly what SPMD
    code inside a 2D ``shard_map`` needs for static per-leaf slicing."""
    if not spec.leaf_aligned:
        raise ValueError("leaf_row_ranges needs a leaf_align=True spec")
    ends = spec.offsets[1:] + (spec.local_rows * LANE,)
    return tuple((o // LANE, e // LANE)
                 for o, e in zip(spec.offsets, ends))


def _check_congruent(leaves, spec: PackSpec) -> None:
    got = tuple(tuple(l.shape) for l in leaves)
    if got != spec.shapes:
        raise ValueError(f"tree does not match spec: {got} vs {spec.shapes}")


def local_chunk_elems(spec: PackSpec) -> Tuple[int, ...]:
    """Per-leaf element count of ONE row-shard block's slice of the leaf
    (the whole padded segment when ``row_shards == 1``). Requires the
    leaf-aligned layout. These are the static slice lengths every shard
    shares — the shard-invariance the 2D grad pipeline is built on."""
    if not spec.leaf_aligned:
        raise ValueError("local_chunk_elems needs a leaf_align=True spec")
    return _shard_chunks(spec)


def unpack_local(buf: jax.Array, spec: PackSpec) -> PyTree:
    """Per-leaf *local slices* of one row-shard block of a (row-sharded)
    packed buffer — the model-parallel counterpart of :func:`unpack`.

    ``buf`` is one shard's ``(K_local, local_rows, LANE)`` block (what a
    device holds inside a 2D ``shard_map``; ``K_local`` is usually 1).
    Returns a pytree congruent with the spec's treedef whose leaf ``i`` is
    the flat ``(K_local, local_chunk_elems(spec)[i])`` slice of that leaf's
    local row range, cast to the leaf's dtype. Padding slots are KEPT
    (zero-filled by ``pack``), so chunk ``j`` is exactly elements
    ``[j*c, (j+1)*c)`` of the padded flat leaf: the layout is
    shard-invariant, no cross-device dependence, and concatenating the M
    chunks reproduces :func:`unpack`.

    Built from plain slicing, so it is linear and jax-differentiable: the
    AD transpose of ``unpack_local`` deposits cotangents straight back
    into the local block (zeros in the inter-leaf padding) — gradients of
    a loss evaluated on local slices arrive packed, per shard, for free.
    """
    if not spec.stacked:
        raise ValueError("unpack_local needs a stacked spec")
    chunks = local_chunk_elems(spec)
    if buf.ndim != 3 or buf.shape[1] * buf.shape[2] != spec.local_rows * LANE:
        raise ValueError(
            f"unpack_local expects one (K_local, {spec.local_rows}, {LANE}) "
            f"row-shard block; got {tuple(buf.shape)}")
    flat = buf.reshape(buf.shape[0], -1)
    leaves = [flat[:, o:o + c].astype(dt)
              for o, c, dt in zip(spec.offsets, chunks, spec.dtypes)]
    return jax.tree_util.tree_unflatten(spec.treedef, leaves)


def mirror_local(tree: PyTree, spec: PackSpec, shard_idx) -> PyTree:
    """Slice a *replicated per-worker* pytree into the local-chunk layout
    of shard ``shard_idx`` — the congruence partner of :func:`unpack_local`
    for data that is NOT packed (batch targets, masks, regularizer
    anchors). Leaf shapes are the per-worker shapes (no leading K dim).

    Returns flat ``(local_chunk_elems[i],)`` leaves, zero-padded exactly
    like the packed layout, so elementwise losses can be evaluated
    chunk-against-chunk with a single psum over the model axis.
    ``shard_idx`` may be a traced value (``jax.lax.axis_index``) — the
    slice start is dynamic but the slice length is static."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if treedef != spec.treedef:
        raise ValueError(f"tree does not match spec treedef: {treedef} "
                         f"vs {spec.treedef}")
    chunks = local_chunk_elems(spec)
    got = tuple(tuple(l.shape) for l in leaves)
    want = tuple(s[1:] for s in spec.shapes)
    if got != want:
        raise ValueError(
            f"mirror_local needs per-worker leaf shapes {want}; got {got}")
    idx = jnp.asarray(shard_idx, jnp.int32)
    out = []
    for leaf, c, sz in zip(leaves, chunks, spec.sizes):
        flat = leaf.reshape(-1)
        seg = c * spec.row_shards
        if seg > sz:
            flat = jnp.pad(flat, (0, seg - sz))
        out.append(jax.lax.dynamic_slice(flat, (idx * c,), (c,)))
    return jax.tree_util.tree_unflatten(spec.treedef, out)


def _shard_chunks(spec: PackSpec) -> Tuple[int, ...]:
    """Per-leaf element count within one shard block (== full segment when
    row_shards == 1)."""
    ends = spec.offsets[1:] + (spec.local_rows * LANE,)
    return tuple(e - o for o, e in zip(spec.offsets, ends))


def _segment_pads(spec: PackSpec) -> Tuple[int, ...]:
    """Zero-fill element count after each leaf's true data (whole segment
    across all row shards)."""
    return tuple(c * spec.row_shards - sz
                 for c, sz in zip(_shard_chunks(spec), spec.sizes))


def pack(tree: PyTree, spec: PackSpec, dtype: Any = None) -> jax.Array:
    """Flatten ``tree`` into a (rows, LANE) — or (K, rows, LANE) — buffer.

    ``dtype`` defaults to the widest dtype among the leaves; padding is
    zeros (the kernels' reductions are pad-safe for zero fill, and the
    optimizer kernels map zeros to zeros so resident padding stays zero)."""
    leaves = jax.tree_util.tree_leaves(tree)
    _check_congruent(leaves, spec)
    _require_float([l.dtype for l in leaves], "pack()")
    dt = jnp.dtype(dtype) if dtype is not None else jnp.result_type(*leaves)
    pads = _segment_pads(spec)
    if spec.stacked:
        parts = []
        for l, pad in zip(leaves, pads):
            flat = l.reshape(spec.k, -1).astype(dt)
            if pad:
                flat = jnp.pad(flat, ((0, 0), (0, pad)))
            parts.append(flat)
        if spec.row_shards > 1:
            # row-sharded layout: shard block j is chunk j of every leaf's
            # segment, in leaf order. Lane-aligned slices, not a
            # (K, M, chunk) reshape: on a TPU that reshape relayouts the
            # whole buffer and takes minutes to compile
            parts = [f[:, j * c:(j + 1) * c]
                     for j in range(spec.row_shards)
                     for f, c in zip(parts, _shard_chunks(spec))]
        flat = parts[0] if len(parts) == 1 else jnp.concatenate(parts,
                                                                axis=1)
        return flat.reshape(spec.k, spec.rows, LANE)
    parts = []
    for l, pad in zip(leaves, pads):
        flat = l.reshape(-1).astype(dt)
        parts.append(jnp.pad(flat, (0, pad)) if pad else flat)
    flat = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    return flat.reshape(spec.rows, LANE)


def _unpack_one_row(row: jax.Array, spec: PackSpec) -> PyTree:
    """Decode ONE worker row — a ``(rows, LANE)`` slice of a stacked
    buffer — into the per-worker param pytree (leaf shapes without the
    leading K dim). Shared by :func:`unpack_worker` / :func:`unpack_mean`."""
    per_worker = tuple(s[1:] for s in spec.shapes)
    if spec.row_shards == 1 and spec.leaf_aligned:
        leaves = _leaf_rows(row, spec, per_worker)
    elif spec.row_shards > 1:
        flat = row.reshape(spec.row_shards, -1)
        leaves = [
            flat[:, o:o + c].reshape(-1)[:sz].astype(dt).reshape(shape)
            for o, c, sz, dt, shape in zip(spec.offsets,
                                           _shard_chunks(spec),
                                           spec.sizes, spec.dtypes,
                                           per_worker)
        ]
    else:
        flat = row.reshape(-1)
        leaves = [
            flat[o:o + sz].astype(dt).reshape(shape)
            for o, sz, dt, shape in zip(spec.offsets, spec.sizes,
                                        spec.dtypes, per_worker)
        ]
    return jax.tree_util.tree_unflatten(spec.treedef, leaves)


def unpack_worker(buf: jax.Array, spec: PackSpec, k: int) -> PyTree:
    """Worker ``k``'s param pytree straight from the stacked buffer.

    The unpack-once publish path: materializes ONE worker's per-worker
    tree (leaf shapes WITHOUT the leading K dim) by slicing its
    ``(rows, LANE)`` row block — reading 1/K of the buffer — instead of
    the full K-way :func:`unpack` followed by a per-worker slice.
    Handles both the leaf-aligned and the row-sharded (``row_shards=M``)
    resident layouts; under GSPMD a sharded buffer contributes only the
    addressed worker's shards.
    """
    if not spec.stacked:
        raise ValueError("unpack_worker needs a stacked spec")
    k = int(k)
    if not 0 <= k < spec.k:
        raise ValueError(f"worker index {k} out of range for K={spec.k}")
    if buf.shape != spec.buf_shape():
        raise ValueError(
            f"buffer shape {tuple(buf.shape)} does not match spec "
            f"{spec.buf_shape()}")
    return _unpack_one_row(buf[k], spec)


def unpack_mean(buf: jax.Array, spec: PackSpec) -> PyTree:
    """The consensus-mean param pytree straight from the stacked buffer.

    Reduces the worker dim IN THE PACKED DOMAIN (one ``(rows, LANE)``
    mean buffer, computed in the buffer's storage dtype — the widest
    participating float) and decodes that single row block, so exactly
    one per-worker tree is materialized. Bit-identical to
    ``mean_params(unpack(buf, spec))`` for f32 trees, without unpacking
    K per-worker copies first.
    """
    if not spec.stacked:
        raise ValueError("unpack_mean needs a stacked spec")
    if buf.shape != spec.buf_shape():
        raise ValueError(
            f"buffer shape {tuple(buf.shape)} does not match spec "
            f"{spec.buf_shape()}")
    return _unpack_one_row(jnp.mean(buf, axis=0), spec)


def _leaf_rows(buf: jax.Array, spec: PackSpec, shapes) -> list:
    """Leaves of a leaf-aligned buffer, each cut out of its own row range
    (``buf`` is ``(..., rows, LANE)``). Slicing rows keeps the buffer in
    its tiled ``(rows, LANE)`` layout: only each leaf is reshaped, never
    the whole buffer, which on a TPU would be a relayout copy of all of
    it (and a second one in the gradient's transpose)."""
    lead = buf.shape[:-2]
    out = []
    for (r0, r1), sz, dt, shape in zip(leaf_row_ranges(spec), spec.sizes,
                                       spec.dtypes, shapes):
        seg = buf[..., r0:r1, :].reshape(lead + (-1,))
        if sz != (r1 - r0) * LANE:
            seg = seg[..., :sz]
        out.append(seg.astype(dt).reshape(shape))
    return out


def unpack(buf: jax.Array, spec: PackSpec) -> PyTree:
    """Exact inverse of ``pack``: strip padding, split, restore per-leaf
    shape and dtype."""
    if spec.stacked:
        if spec.row_shards == 1 and spec.leaf_aligned:
            return jax.tree_util.tree_unflatten(
                spec.treedef, _leaf_rows(buf, spec, spec.shapes))
        if spec.row_shards > 1:
            # inverse of the row-sharded layout: gather each leaf's M
            # chunks (one per shard block), re-join, strip padding
            flat = buf.reshape(spec.k, spec.row_shards, -1)
            leaves = [
                flat[:, :, o:o + c].reshape(spec.k, -1)[:, :sz]
                .astype(dt).reshape(shape)
                for o, c, sz, dt, shape in zip(spec.offsets,
                                               _shard_chunks(spec),
                                               spec.sizes, spec.dtypes,
                                               spec.shapes)
            ]
            return jax.tree_util.tree_unflatten(spec.treedef, leaves)
        flat = buf.reshape(spec.k, -1)
        leaves = [
            flat[:, o:o + sz].astype(dt).reshape(shape)
            for o, sz, dt, shape in zip(spec.offsets, spec.sizes,
                                        spec.dtypes, spec.shapes)
        ]
    else:
        flat = buf.reshape(-1)
        leaves = [
            flat[o:o + sz].astype(dt).reshape(shape)
            for o, sz, dt, shape in zip(spec.offsets, spec.sizes,
                                        spec.dtypes, spec.shapes)
        ]
    return jax.tree_util.tree_unflatten(spec.treedef, leaves)
