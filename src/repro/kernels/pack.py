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

import contextvars
import functools
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.tracing import scoped

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


@scoped("unpack")
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


@scoped("pack")
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


@scoped("unpack")
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


@scoped("unpack")
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


@scoped("unpack")
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


# ---------------------- narrow leaves as lane-dense rows ---------------------
#
# A leaf whose rows are narrower than a lane, an embedding table (N, 10)
# say, sits lane-dense in its packed segment, but its (N, E) view is
# lane-padded to 128 on a TPU and relaid both ways around every use. Inside
# :func:`worker_map`, :func:`take_rows` instead reads such a leaf's rows
# straight from the worker's (rows, LANE) buffer and puts their gradient
# into a block of the same shape. Only this module knows that layout.

_ROW_BLOCKS: contextvars.ContextVar = contextvars.ContextVar(
    "row_blocks", default=None)


class _RowBlock(NamedTuple):
    """Where a leaf lives in one worker's packed buffer: element
    ``j`` of the flattened leaf is element ``start * LANE + j`` of the
    flattened ``buf`` (``(rows, LANE)``), inside rows ``[start, stop)``."""
    buf: jax.Array
    start: int
    stop: int


def _row_block(leaf: Any) -> Optional[_RowBlock]:
    """The :class:`_RowBlock` of ``leaf`` when it is a leaf handed to the
    function :func:`worker_map` runs; ``None`` for any other array."""
    entry = (_ROW_BLOCKS.get() or {}).get(id(leaf))
    return entry[1] if entry is not None and entry[0] is leaf else None


def worker_map(fn: Callable, buf: jax.Array, spec: PackSpec,
               params: PyTree, *args) -> Any:
    """``jax.vmap(fn)(params, *args)`` over the worker dim, where
    ``params`` are the views :func:`unpack` gave of ``buf``, or those
    views under sharding constraints. Inside ``fn``, :func:`take_rows` on
    a leaf of a stacked, leaf-aligned buffer with one row shard reads the
    worker's rows of ``buf`` (any other layout keeps ``table[ids]``), so
    a view that no other op reads is never built."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    ranges = ()
    if spec.stacked and spec.row_shards == 1 and spec.leaf_aligned:
        ranges = leaf_row_ranges(spec)

    def one(leaves, rows, *args):
        token = _ROW_BLOCKS.set({id(leaf): (leaf, _RowBlock(rows, *r))
                                 for leaf, r in zip(leaves, ranges)})
        try:
            return fn(jax.tree_util.tree_unflatten(treedef, leaves), *args)
        finally:
            _ROW_BLOCKS.reset(token)

    return jax.vmap(one)(leaves, buf, *args)


def take_rows(table: jax.Array, ids: jax.Array) -> jax.Array:
    """``table[ids]``: the rows of an ``(N, E)`` table (or the entries of
    an ``(N,)`` one) at integer ``ids``, with ``table[ids]``'s handling of
    negative and out-of-range ids.

    Where ``table`` is a leaf :func:`worker_map` handed over unchanged and
    its rows are narrower than a lane (``E < LANE``), the rows are read
    from the buffer's own lane-dense ``(rows, LANE)`` tiles and their
    gradient is scatter-added into tiles of the same shape. Anywhere else,
    and for rows a lane wide or wider, it is exactly ``table[ids]``.
    Values and gradients are those of ``table[ids]``; only the order in
    which the cotangents of repeated ids are summed may differ."""
    where = _row_block(table)
    e = table.shape[1] if table.ndim == 2 else 1
    if (where is None or table.ndim > 2 or e >= LANE
            or where.buf.size >= 2 ** 31):
        return table[ids]
    n = table.shape[0]
    with jax.named_scope("lookup"):
        flat = ids.reshape(-1).astype(jnp.int32)
        flat = jnp.where(flat < 0, flat + n, flat)
        # as table[ids]: an out-of-range id reads the clamped row and its
        # cotangent is dropped
        valid = (flat >= 0) & (flat < n)
        out = _lane_rows(where.buf, jnp.clip(flat, 0, n - 1), valid, e,
                         where.start, where.stop)
    return out.reshape(ids.shape + table.shape[1:]).astype(table.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _lane_rows(buf: jax.Array, ids: jax.Array, valid: jax.Array, e: int,
               start: int, stop: int) -> jax.Array:
    """``(len(ids), e)`` rows, ``e < LANE``, of the table whose flat
    elements start at element ``start * LANE`` of ``buf`` (``(rows,
    LANE)``) and lie in its rows ``[start, stop)``: row ``i`` is ``e``
    elements at ``i * e`` from there, inside the window of buffer rows
    :func:`_window` gives. Those rows are gathered whole, and ``e`` lanes
    are selected out of them by a masked sum (one term is nonzero, so it
    is exact)."""
    win, r, off, hit = _window(ids * e, e, stop - start)
    windows = jnp.concatenate([buf[start + r + i] for i in range(win)],
                              axis=-1)
    return jnp.sum(jnp.where(hit, windows[:, None, :], 0), axis=-1)


def _window(at: jax.Array, e: int, rows: int):
    """``(win, r, off, hit)``: the ``e`` elements at flat offset ``at`` of
    a block of ``rows`` buffer rows lie at lanes ``[off, off + e)`` of its
    rows ``[r, r + win)``, and ``hit[j, c, l]`` (``(len(at), e, win *
    LANE)``) says that lane ``l`` of id ``j``'s window holds its element
    ``c``. One row holds them when ``e`` divides LANE or the block is one
    row; two may otherwise, and ``r`` is clamped so that both lie in the
    block (``off`` is then in ``[0, win * LANE - e]``)."""
    win = 1 if LANE % e == 0 or rows < 2 else 2
    r = jnp.minimum(at // LANE, rows - win)
    off = at - r * LANE
    want = off[:, None, None] + jnp.arange(e)[None, :, None]
    return win, r, off, jnp.arange(win * LANE)[None, None, :] == want


def _lane_rows_fwd(buf, ids, valid, e, start, stop):
    return (_lane_rows(buf, ids, valid, e, start, stop),
            (ids, valid, buf.shape))


def _lane_rows_bwd(e, start, stop, res, ct):
    """The transpose of :func:`_lane_rows`: each valid id's cotangent is
    added into the table's block as whole buffer rows, and the block is
    padded to the buffer's shape (XLA adds the pad into the sum of the
    leaves' gradients).

    An id's window takes its cotangent through the forward's own mask,
    so its ``win`` rows of LANE floats hold the ``e`` floats at their
    lanes and zeros elsewhere. One scatter per row of the window adds
    those rows at ``r + i``: a scatter loops once per id, not once per
    float, and sums repeated rows itself. (The TPU compiler turns a
    scatter of two-row windows into a ``while`` loop, so the rows go one
    at a time.) An invalid id, and a row of a window that holds none of
    the id's floats, get a row past the block's end, which the scatter
    drops. Rows of one element scatter as single floats, which is already
    one update an id."""
    ids, valid, shape = res
    rows = stop - start
    g = jnp.zeros((rows, LANE), ct.dtype)
    at = ids * e
    if e == 1:
        row = jnp.where(valid, at // LANE, rows)
        g = g.at[row, at % LANE].add(ct[:, 0], mode="drop")
    else:
        win, r, off, hit = _window(at, e, rows)
        windows = jnp.sum(jnp.where(hit, ct[:, :, None], 0), axis=1)
        for i in range(win):
            some = valid & (off < (i + 1) * LANE) & (off + e > i * LANE)
            g = g.at[jnp.where(some, r + i, rows)].add(
                windows[:, i * LANE:(i + 1) * LANE], mode="drop")
    return jnp.pad(g, ((start, shape[0] - stop), (0, 0))), None, None


_lane_rows.defvjp(_lane_rows_fwd, _lane_rows_bwd)
