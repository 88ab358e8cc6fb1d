"""Runner-side CSR graph blocks: the JAX half of graph/csr.py.

The serving process ships rows/cols edge arrays once per cache epoch.
Two kernels read them:

- the SET hop (`multi_hop`): a `+collect` level arrives as a [B, n] batch
  of start-node masks (the cross-query batcher stacks concurrent
  traversals) and leaves as the reached-node masks — frontiers never
  materialize id values between hops (jax.lax.scan over gather +
  scatter-or);
- the BAG hop (`bag_hop`): a folded `->edge->node` chain arrives as a
  [B, C0] batch of start-node INDEX lists and leaves as the last level's
  index lists, one entry a path, duplicates kept, in the order of
  `graph/csr.py hop_bag_idx` (sources in frontier order, each source's
  destinations in edge order). It walks the CSR proper (`indptr`, the
  destination column in source order) with static capacities a level and
  builds nothing of the graph's size.
"""

from __future__ import annotations

from functools import lru_cache

import numpy as np

# -- the bag hop's static shapes -----------------------------------------------
#
# A rider rides one RUNG of a short capacity ladder: rung r allows level l
# (1-based) `bag_caps(...)[r][l-1]` paths. Rung 0 is sized from the graph's
# mean out-degree so that ordinary sources fit with room (4x the mean
# fan-out, at least 64 a level); each further rung is 4x the one before.
# A rider whose true total at some level passes its rung's capacity is
# re-dispatched on a higher rung (graph/csr.py); past the top rung the
# host walk answers.
BAG_RUNGS = 4
BAG_MIN_CAP = 64
BAG_MAX_CAP = 1 << 18  # the ladder's top: 1 MB of ids a rider
# the start list rides a power of two too; a longer list takes the host walk
BAG_MAX_START = 1024
# rider buckets whose first-rung programs a ship pre-compiles (1, 2, 4, ...)
BAG_PREWARM_RIDERS = 32


def pow2_at_least(x: int) -> int:
    p = 1
    while p < x:
        p *= 2
    return p


@lru_cache(maxsize=256)
def bag_caps(n_nodes: int, n_edges: int, c0: int, hops: int) -> tuple:
    """The capacity ladder for `hops` levels from `c0` start slots on a
    graph of this mean out-degree: [rung][level] -> paths allowed."""
    deg = max(float(n_edges) / max(int(n_nodes), 1), 1.0)
    ladder = []
    for r in range(BAG_RUNGS):
        caps, reach = [], float(c0)
        for _ in range(hops):
            reach *= deg
            caps.append(pow2_at_least(
                max(int(np.ceil(4.0 * reach)), BAG_MIN_CAP)) * 4 ** r)
        if max(caps) > BAG_MAX_CAP:
            break
        ladder.append(tuple(caps))
    return tuple(ladder)


def pad_len(x: int) -> int:
    """Array lengths the bag programs are compiled for: `x` rounded up to
    a multiple of an eighth of the power of two below it (at least 1,024),
    so that a graph that grows by a few edges keeps its programs."""
    x = max(int(x), 1)
    q = max(pow2_at_least(x + 1) // 16, 1024)
    return -(-x // q) * q


def _multi_hop_impl(rows, cols, start, n_nodes, hops, union):
    # start: [B, n_nodes] bool — every rider's frontier advances in the
    # same gather + scatter-or, batched along the leading axis
    import jax
    import jax.numpy as jnp

    def hop(frontier, _):
        contrib = frontier[:, rows].astype(jnp.int32)  # [B, E]
        nxt = (
            jnp.zeros(frontier.shape, jnp.int32).at[:, cols].add(contrib)
            > 0
        )
        return nxt, nxt

    frontier, layers = jax.lax.scan(hop, start, None, length=hops)
    if union:
        return layers.any(axis=0)
    return frontier


_jit_cache: dict = {}


def _multi_hop_jit(rows, cols, start, n_nodes, hops, union):
    import jax

    ck = (n_nodes, hops, union, rows.shape[0], start.shape[0])
    fn = _jit_cache.get(ck)
    if fn is None:
        from surrealdb_tpu.device.kernelstats import note_compile

        note_compile("csr_multi_hop")
        fn = jax.jit(_multi_hop_impl, static_argnums=(3, 4, 5))
        _jit_cache[ck] = fn
    else:
        from surrealdb_tpu.device.kernelstats import note_hit

        note_hit("csr_multi_hop")
    return fn(rows, cols, start, n_nodes, hops, union)


def _bag_hop_impl(indptr, cols, packed, caps):
    """`len(caps)` bag levels for B riders. `packed` is [B, 1 + C0] int32:
    a rider's start count, then its start node indexes. Returns
    [B, hops + caps[-1]] int32: every level's TRUE total (what the level
    holds without a capacity), then the last level's node indexes, the
    first `min(total, cap)` of them live.

    One level, for a frontier f of `n` live entries: degrees by two
    gathers of `indptr`, their inclusive cumsum `cum` (source i's paths
    are output positions cum[i-1] .. cum[i]-1), and for output position p
    the column position `p + base[src(p)]`, base[i] = indptr[f[i]] -
    cum[i-1]. `base[src(p)]` needs no search and no gather: src(p) is the
    number of sources whose segment starts at or before p, so it is
    base[0] plus every later step of `base` whose segment start is <= p:
    one compare-and-sum over [B, cap, C_in] that the compiler fuses into
    a reduction (no array of that shape exists). Then one gather of the
    column."""
    import jax.numpy as jnp

    n, f = packed[:, 0], packed[:, 1:]
    totals = []
    for cap in caps:
        live = jnp.arange(f.shape[1], dtype=jnp.int32)[None, :] < n[:, None]
        lo = jnp.where(live, indptr[f], 0)
        deg = jnp.where(live, indptr[f + 1], 0) - lo
        cum = jnp.cumsum(deg, axis=1)
        # a level of hubs can pass 2**31 paths: the int32 sum wraps, so
        # a float sum says when, and the total then reads saturated
        total = jnp.where(
            jnp.sum(deg.astype(jnp.float32), axis=1) > 2.0 ** 30,
            jnp.int32(2 ** 31 - 1), cum[:, -1])
        seg = cum - deg                       # where source i's paths start
        base = lo - seg
        step = jnp.concatenate(
            [base[:, :1], base[:, 1:] - base[:, :-1]], axis=1)
        p = jnp.arange(cap, dtype=jnp.int32)
        off = jnp.sum(jnp.where(seg[:, None, :] <= p[None, :, None],
                                step[:, None, :], 0), axis=2)
        ok = p[None, :] < total[:, None]
        f = jnp.where(ok, cols[jnp.where(ok, p[None, :] + off, 0)], 0)
        n = jnp.minimum(total, cap)
        totals.append(total)
    return jnp.concatenate([jnp.stack(totals, axis=1), f], axis=1)


@lru_cache(maxsize=None)
def _bag_jit():
    import jax

    return jax.jit(_bag_hop_impl, static_argnums=(3,))


def _bag_hop_jit(indptr, cols, packed, caps):
    from surrealdb_tpu.device.kernelstats import note_shape

    note_shape("csr_bag_hop", (indptr.shape[0], cols.shape[0],
                               packed.shape, caps))
    return _bag_jit()(indptr, cols, packed, caps)


class CsrStore:
    """Device-resident adjacency for ONE graph cache epoch. The edge
    arrays are kept in source order (sorted here if they were not
    shipped so: the set hop reads them in any order, the bag hop needs
    this one), so one copy of the edges serves both kernels' host side;
    on the device the set hop holds them at their exact length and the
    bag hop padded (`pad_len`), each from its first use on."""

    def __init__(self, key: str, rows: np.ndarray, cols: np.ndarray,
                 n_nodes: int):
        self.key = key
        self.n_nodes = int(n_nodes)
        if len(rows) > 1 and bool((rows[1:] < rows[:-1]).any()):
            order = np.argsort(rows, kind="stable")
            rows, cols = rows[order], cols[order]
        self.rows = rows
        self.cols = cols
        self.device = None
        self.bag_device = None

    def nbytes(self) -> int:
        return int(self.rows.nbytes + self.cols.nbytes)

    def device_nbytes(self) -> int:
        """Device-resident bytes once both kernels have run: the two
        edge arrays as they are (set hop), `indptr` and the padded
        column (bag hop). Runner byte-budget ledger."""
        return self.nbytes() + 4 * (pad_len(self.n_nodes + 1)
                                    + pad_len(len(self.cols)))

    def _ensure(self):
        if self.device is None:
            import jax.numpy as jnp

            self.device = (jnp.asarray(self.rows), jnp.asarray(self.cols))
        return self.device

    def _ensure_bag(self):
        """(indptr, column) on the device, padded: `indptr` with the edge
        count (a node past the last has no edges), the column with
        zeros no position ever names."""
        if self.bag_device is None:
            import jax.numpy as jnp

            e = len(self.cols)
            indptr = np.full(pad_len(self.n_nodes + 1), e, np.int32)
            indptr[:self.n_nodes + 1] = np.searchsorted(
                self.rows, np.arange(self.n_nodes + 1), side="left")
            cols = np.zeros(pad_len(e), np.int32)
            cols[:e] = self.cols
            self.bag_device = (jnp.asarray(indptr), jnp.asarray(cols))
        return self.bag_device

    def bag_hop(self, packed: np.ndarray, caps: tuple):
        """[B, 1 + C0] int32 riders (count, start indexes) -> (totals
        [B, hops] int32, flat int32): every level's true total a rider,
        and the riders' last levels end to end, nothing for a rider that
        passed a capacity (its totals say where). Batch sizes round up
        to a power of two, as in `multi_hop`."""
        import jax.numpy as jnp

        from surrealdb_tpu.device.kernelstats import CSR, phase

        indptr_d, cols_d = self._ensure_bag()
        caps = tuple(int(c) for c in caps)
        hops, b = len(caps), packed.shape[0]
        bucket = pow2_at_least(b)
        if bucket != b:
            packed = np.concatenate(
                [packed, np.zeros((bucket - b, packed.shape[1]), np.int32)])
        with phase("h2d"):
            packed_d = jnp.asarray(packed)
        with phase("device"):
            out = np.asarray(_bag_hop_jit(indptr_d, cols_d, packed_d, caps))
        with phase("d2h"):
            totals = np.ascontiguousarray(out[:b, :hops])
            fits = (totals <= np.asarray(caps, np.int32)).all(axis=1)
            keep = np.where(fits, totals[:, -1], 0)
            flat = out[:b, hops:][
                np.arange(caps[-1], dtype=np.int32)[None, :] < keep[:, None]]
        CSR["bag_riders"] += int(fits.sum())
        CSR["overflows"] += int(b - fits.sum())
        CSR["paths_out"] += int(keep.sum())
        CSR["edges_gathered"] += int(totals[fits].sum())
        return totals, flat

    def multi_hop(self, start: np.ndarray, hops: int,
                  union: bool) -> np.ndarray:
        """[B, n] (or legacy [n]) start masks -> same-shaped reached
        masks. Batch sizes round up to a power of two so the compiled
        kernel shapes stay a bounded ladder under dynamic batching."""
        import jax.numpy as jnp

        from surrealdb_tpu.device.kernelstats import phase

        rows_d, cols_d = self._ensure()
        single = start.ndim == 1
        masks = start[None, :] if single else start
        b = masks.shape[0]
        bucket = pow2_at_least(b)
        if bucket != b:
            masks = np.concatenate(
                [masks, np.zeros((bucket - b, masks.shape[1]),
                                 masks.dtype)]
            )
        # the op's timeline, as in VecStore.knn (kernelstats.phase): the
        # one output's arrival on the host ends `device`
        with phase("h2d"):
            masks_d = jnp.asarray(masks.astype(bool))
        with phase("device"):
            out = np.asarray(_multi_hop_jit(
                rows_d, cols_d, masks_d,
                self.n_nodes, int(hops), bool(union),
            ))
        with phase("d2h"):
            out = out[:b].astype(np.uint8)
        return out[0] if single else out
