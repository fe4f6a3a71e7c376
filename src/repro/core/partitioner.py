"""Unified Partitioner subsystem — the common layer over every partitioning
strategy in the repo (paper §3 + Appendix D).

All backends (``dlv`` — Algorithm 6, ``kdtree`` — the SketchRefine baseline,
``bucketing`` — the out-of-core Appendix D.2 scheme) produce the same
:class:`Partition`: group ids, a permutation making groups contiguous
slices, per-group representatives/bounding boxes, and a *flat array split
tree* answering GetGroup for one tuple (scalar descent) or a whole batch
(vectorized descent, optionally jitted through ``lax.while_loop``).

Select a backend by name::

    from repro.core import partitioner
    part = partitioner.fit(X, backend="dlv", d_f=100)
    part.get_group(X[0])          # scalar GiST-style descent
    part.get_group_batch(X[:1000])  # one vectorized descent for all rows

Group statistics (representatives = member means, boxes = member min/max)
are produced by :func:`group_stats` — a single vectorized ``reduceat`` pass
in memory, or a chunked accumulation that optionally runs each chunk's
count/sum/sum-of-squares on a device mesh (shard_map + psum, the
``kernels/segstats.py`` role) so layer-0 stats at 10^8+ tuples never
require a host-side sorted copy of the relation.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Callable, Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

# Chunks the mesh group-stats pass has dispatched, and the most devices
# one of them was sharded over (mutated under _MESH_STATS_LOCK)
SHARED_MUTABLE = ("_MESH_STATS",)
_MESH_STATS = {"chunks": 0, "devices": 0}
_MESH_STATS_LOCK = threading.Lock()


def mesh_stats_counts() -> dict:
    """Dispatch counters of the mesh group-stats pass (atomic snapshot):
    ``chunks`` run sharded, and the most ``devices`` one spanned."""
    with _MESH_STATS_LOCK:
        return dict(_MESH_STATS)


# ---------------------------------------------------------------- split tree


@dataclasses.dataclass
class SplitTree:
    """Flat array split tree (replaces the old ``List[SplitNode]`` pointers).

    Node ``i`` splits on attribute ``attr[i]`` with ascending boundary
    values ``bounds[bound_off[i]:bound_off[i+1]]``; its ``b_i + 1`` children
    (``b_i`` = number of bounds) live at ``children[bound_off[i] + i :]`` —
    the child base is ``bound_off[i] + i`` because every node has exactly
    one more child than bounds, so no second offset array is needed.
    ``children`` entries >= 0 are node ids; entries < 0 encode leaf group
    ids as ``~gid``.  ``root`` is a node id, or ``~gid`` when the partition
    never split (single group).
    """
    attr: np.ndarray          # (N,) int32
    bound_off: np.ndarray     # (N+1,) int64
    bounds: np.ndarray        # (B,) float64
    children: np.ndarray      # (B+N,) int64
    root: int

    @property
    def num_nodes(self) -> int:
        return len(self.attr)

    @staticmethod
    def single_leaf() -> "SplitTree":
        return SplitTree(np.zeros(0, np.int32), np.zeros(1, np.int64),
                         np.zeros(0, np.float64), np.zeros(0, np.int64), ~0)

    def descend(self, t: np.ndarray) -> int:
        """Scalar GetGroup: sub-linear split-tree descent (GiST analogue)."""
        node = int(self.root)
        while node >= 0:
            b0, b1 = self.bound_off[node], self.bound_off[node + 1]
            pos = b0 + np.searchsorted(self.bounds[b0:b1],
                                       t[self.attr[node]], side="right")
            node = int(self.children[node + pos])
        return ~node

    def descend_batch(self, T: np.ndarray) -> np.ndarray:
        """Vectorized GetGroup over a (m, k) batch of tuples.

        All rows descend in lock-step: one vectorized binary search per
        tree level over each row's private bounds slice (ragged slices, so
        a masked manual bisection instead of ``np.searchsorted``).
        """
        T = np.asarray(T, np.float64)
        cur = np.full(T.shape[0], self.root, np.int64)
        if self.num_nodes == 0:
            return ~cur
        act = np.flatnonzero(cur >= 0)
        while len(act):
            nodes = cur[act]
            vals = T[act, self.attr[nodes]]
            lo = self.bound_off[nodes].copy()
            hi = self.bound_off[nodes + 1].copy()
            live = lo < hi
            while live.any():
                mid = (lo + hi) >> 1
                take = live & (self.bounds[np.minimum(mid, len(self.bounds)
                                                      - 1)] <= vals)
                lo = np.where(take, mid + 1, lo)
                hi = np.where(live & ~take, mid, hi)
                live = lo < hi
            cur[act] = self.children[nodes + lo]   # child base = bound_off+node
            act = act[cur[act] >= 0]
        return ~cur

    def descend_batch_jax(self, T) -> jax.Array:
        """Jit-able batch GetGroup (``lax.while_loop`` over tree levels)."""
        T = jnp.asarray(T)
        if self.num_nodes == 0:
            return jnp.full(T.shape[0], ~int(self.root), jnp.int64)
        # nodes may all be bound-less (single-child chains, e.g. a merged
        # single-bucket tree): pad with a sentinel so the traced gather in
        # the bisect body never reads from a size-0 array
        bounds = self.bounds if len(self.bounds) else np.array([np.inf])
        return _descend_batch_jax(jnp.asarray(self.attr),
                                  jnp.asarray(self.bound_off),
                                  jnp.asarray(bounds, T.dtype),
                                  jnp.asarray(self.children),
                                  int(self.root), T)


@jax.jit
def _descend_batch_jax(attr, bound_off, bounds, children, root, T):
    m = T.shape[0]
    rows = jnp.arange(m)

    def level(cur):
        node = jnp.maximum(cur, 0)
        vals = T[rows, attr[node]]
        lo0 = bound_off[node]

        def bisect_body(state):
            lo, hi = state
            live = lo < hi
            mid = (lo + hi) >> 1
            take = live & (bounds[jnp.minimum(mid, bounds.shape[0] - 1)]
                           <= vals)
            return (jnp.where(take, mid + 1, lo),
                    jnp.where(live & ~take, mid, hi))

        lo, _ = jax.lax.while_loop(lambda s: jnp.any(s[0] < s[1]),
                                   bisect_body, (lo0, bound_off[node + 1]))
        nxt = children[node + lo]
        return jnp.where(cur >= 0, nxt, cur)

    cur = jax.lax.while_loop(lambda c: jnp.any(c >= 0), level,
                             jnp.full(m, root, jnp.int64))
    return ~cur


# ----------------------------------------------------------------- Partition


@dataclasses.dataclass
class Partition:
    """Common result of every partitioning backend (``fit``)."""
    gid: np.ndarray           # (n,) group id per tuple
    order: np.ndarray         # permutation; groups are contiguous slices
    offsets: np.ndarray       # (G+1,) slice bounds into order
    reps: np.ndarray          # (G, k) group means (representative tuples)
    boxes_lo: np.ndarray      # (G, k) member min per attr
    boxes_hi: np.ndarray      # (G, k)
    tree: SplitTree

    @property
    def num_groups(self) -> int:
        return len(self.offsets) - 1

    @property
    def counts(self) -> np.ndarray:
        return np.diff(self.offsets)

    def members(self, g: int) -> np.ndarray:
        return self.order[self.offsets[g]:self.offsets[g + 1]]

    def members_batch(self, gs: np.ndarray) -> np.ndarray:
        """Concatenated members of groups ``gs`` (one vectorized gather)."""
        gs = np.asarray(gs, np.int64)
        starts = self.offsets[gs]
        lens = self.offsets[gs + 1] - starts
        total = int(lens.sum())
        if total == 0:
            return np.zeros(0, np.int64)
        base = np.repeat(starts - np.concatenate(
            [[0], np.cumsum(lens)[:-1]]), lens)
        return self.order[base + np.arange(total)]

    def get_group(self, t: np.ndarray) -> int:
        return self.tree.descend(np.asarray(t))

    def get_group_batch(self, T: np.ndarray, *, jit: bool = False):
        if jit:
            return self.tree.descend_batch_jax(T)
        return self.tree.descend_batch(T)


# --------------------------------------------------------- backend registry


_BACKENDS: Dict[str, Callable[..., Partition]] = {}


def register_backend(name: str):
    def deco(fn):
        # repro: allow[REPRO010] backends register while their module
        # is imported, which the interpreter's import lock serializes
        _BACKENDS[name] = fn
        return fn
    return deco


def _ensure_backends() -> None:
    # Importing the strategy modules registers them (kept lazy so this
    # module stays import-cycle-free).
    from repro.core import bucketing, dlv, kdtree  # noqa: F401


def available_backends():
    _ensure_backends()
    return sorted(_BACKENDS)


def fit(X, *, backend: str = "dlv", **kwargs) -> Partition:
    """Partition ``X`` (array, or a ChunkSource for ``bucketing`` — e.g.
    ``Relation.chunk_source()`` for an out-of-core table; the bucketing
    backend also accepts ``mesh=`` to shard its streaming stats passes)."""
    _ensure_backends()
    if backend not in _BACKENDS:
        raise ValueError(f"unknown partitioner backend {backend!r}; "
                         f"have {sorted(_BACKENDS)}")
    return _BACKENDS[backend](X, **kwargs)


# ------------------------------------------------------------- group stats


def _chunk_stats_jit(mesh, G: int, k: int):
    """Per-chunk (count, sum, sumsq) on the mesh: rows sharded over the
    'data' axis, per-device scatter-add partials psum-reduced — the
    shard-level twin of ``kernels.segstats`` (ids must be < G+1; row G is
    the padding bin)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.distributed import shard_map

    axis = mesh.axis_names[0]

    def local(v, i):
        cnt = jnp.zeros(G + 1, v.dtype).at[i].add(1.0)
        s = jnp.zeros((G + 1, k), v.dtype).at[i].add(v)
        q = jnp.zeros((G + 1, k), v.dtype).at[i].add(v * v)
        return (jax.lax.psum(cnt, axis), jax.lax.psum(s, axis),
                jax.lax.psum(q, axis))

    fn = jax.jit(shard_map(local, mesh=mesh,
                           in_specs=(P(axis, None), P(axis)),
                           out_specs=(P(None), P(None, None), P(None, None))))
    vsh = NamedSharding(mesh, P(axis, None))
    ish = NamedSharding(mesh, P(axis))
    return fn, vsh, ish


def group_stats(X: np.ndarray, order: np.ndarray, offsets: np.ndarray, *,
                mesh=None, chunk_rows: Optional[int] = None
                ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(reps, boxes_lo, boxes_hi) for contiguous groups — the one
    finalization pass shared by every backend.

    In-memory default: a single vectorized ``reduceat`` sweep over
    ``X[order]``.  With ``chunk_rows`` set, the sorted relation is consumed
    chunk by chunk and only the (G, k) accumulators live on the host; with
    ``mesh`` also set, each chunk's count/sum pass runs sharded across the
    mesh's leading axis with psum reduction (reps reduced across shards) —
    the layer-0 path for relations whose sorted copy must never
    materialize host-side.
    """
    X = np.asarray(X)
    n, k = X.shape
    G = len(offsets) - 1
    counts = np.diff(offsets).astype(np.float64)
    if chunk_rows is None or n <= chunk_rows:
        Xo = X[order]
        sums = np.add.reduceat(Xo, offsets[:-1], axis=0) \
            if G else np.zeros((0, k))
        lo = np.minimum.reduceat(Xo, offsets[:-1], axis=0) \
            if G else np.zeros((0, k))
        hi = np.maximum.reduceat(Xo, offsets[:-1], axis=0) \
            if G else np.zeros((0, k))
        reps = sums / np.maximum(counts, 1.0)[:, None]
        return reps, lo, hi

    sums = np.zeros((G, k))
    lo = np.full((G, k), np.inf)
    hi = np.full((G, k), -np.inf)
    fn = None
    for a in range(0, n, chunk_rows):
        b = min(a + chunk_rows, n)
        chunk = X[order[a:b]]
        # contiguous layout -> chunk-local ids are sorted ascending
        ids = np.searchsorted(offsets, np.arange(a, b), side="right") - 1
        u0, u1 = int(ids[0]), int(ids[-1])
        if mesh is not None:
            if fn is None:
                fn, vsh, ish = _chunk_stats_jit(mesh, G, k)
            nd = int(mesh.shape[mesh.axis_names[0]])
            # pad every chunk to the same sharded shape: one compilation
            rows = ((chunk_rows + nd - 1) // nd) * nd
            cpad = np.pad(chunk, ((0, rows - len(chunk)), (0, 0)))
            ipad = np.pad(ids, (0, rows - len(ids)), constant_values=G)
            v_dev = jax.device_put(jnp.asarray(cpad), vsh)
            cnt_d, sum_d, _ = fn(v_dev,
                                 jax.device_put(jnp.asarray(ipad), ish))
            sums += np.asarray(sum_d)[:G]
            with _MESH_STATS_LOCK:
                _MESH_STATS["chunks"] += 1
                _MESH_STATS["devices"] = max(_MESH_STATS["devices"],
                                             len(v_dev.sharding.device_set))
        else:
            loc = ids - u0
            nloc = u1 - u0 + 1
            for j in range(k):
                sums[u0:u1 + 1, j] += np.bincount(loc, weights=chunk[:, j],
                                                  minlength=nloc)
        # boxes: reduceat over the chunk's group boundary positions
        bpos = np.concatenate([[0], np.flatnonzero(np.diff(ids)) + 1])
        np.minimum.at(lo, ids[bpos],
                      np.minimum.reduceat(chunk, bpos, axis=0))
        np.maximum.at(hi, ids[bpos],
                      np.maximum.reduceat(chunk, bpos, axis=0))
    reps = sums / np.maximum(counts, 1.0)[:, None]
    return reps, lo, hi


def finalize(X: np.ndarray, order: np.ndarray, offsets: np.ndarray,
             tree: SplitTree, *, mesh=None,
             chunk_rows: Optional[int] = None) -> Partition:
    """Assemble a Partition from the contiguous layout + split tree."""
    n = len(order)
    G = len(offsets) - 1
    gid = np.empty(n, np.int64)
    gid[order] = np.repeat(np.arange(G), np.diff(offsets))
    reps, lo, hi = group_stats(X, order, offsets, mesh=mesh,
                               chunk_rows=chunk_rows)
    return Partition(gid, order, offsets, reps, lo, hi, tree)
