"""Runner-side quantized graph-ANN blocks: the JAX half of idx/cagra.py.

The serving process builds the CAGRA-style index (fixed-out-degree flat
graph + per-row-scaled int8 rows, idx/cagra.py) and ships it once per
build via the same (key, tag) block protocol as the vector store — so
the PR-4 crash/reship discipline and PR-6 prewarm apply unchanged. A
search arrives as a [B, D] f32 query batch and leaves as [B, kc] int32
candidate ids; the exact f32 re-rank happens on the serving side, which
holds the full-precision rows.

The descent kernel is the fixed-iteration, static-shape batched greedy
frontier search of arXiv:2308.15136 (pure gather + top_k — a perfect
fit for the MXU/padded-array discipline): every shape in the loop is
static (frontier width W, expansions E per iteration, out-degree D_out,
iteration count), query batches round up to a power of two, and the
compiled kernels form a bounded ladder exactly like the brute-KNN
bucket ladder. Scoring is int8×int8→int32 on the MXU with per-row
dequant scales (knn_rank_int8's recipe); the routing probe that seeds
the frontier is one [B, P] gemm over a precomputed strided row sample.
"""

from __future__ import annotations

import numpy as np

_jit_cache: dict = {}


def _descent_scored(graph, x8, arow, x2q, x8p, arowp, x2qp, probe_ids,
                    qs, metric, width, iters, expand, kc):
    """Descent core returning BOTH [B, kc] ids and their int8 scores.

    The scored variant exists for the mesh execution layer
    (device/mesh.py): per-device partial descents over row shards merge
    on (score, global-id), so the shard kernel needs the distances the
    single-device kernel throws away."""
    import jax
    import jax.numpy as jnp

    b, _dim = qs.shape
    d_out = graph.shape[1]
    # int8 query quantization (knn_rank_int8's recipe): the MXU runs
    # int8×int8→int32; true dot ≈ dots * arow / sq
    sq = 127.0 / jnp.maximum(jnp.abs(qs).max(axis=1), 1e-30)  # [B]
    q8 = jnp.round(qs * sq[:, None]).astype(jnp.int8)
    inv_sq = 1.0 / sq

    def score_rows(ids):
        # ids [B, C] -> f32 scores (lower = closer)
        rows = x8[ids]                                  # [B, C, D] int8
        dots = jnp.einsum(
            "bcd,bd->bc", rows, q8, preferred_element_type=jnp.int32
        ).astype(jnp.float32) * (arow[ids] * inv_sq[:, None])
        if metric == "euclidean":
            return x2q[ids] - 2.0 * dots
        return -dots  # cosine (pre-normalized rows) / dot

    # routing probe: ONE [B, P] gemm over the precomputed strided rows
    pdots = jnp.einsum(
        "pd,bd->bp", x8p, q8, preferred_element_type=jnp.int32
    ).astype(jnp.float32) * (arowp[None, :] * inv_sq[:, None])
    if metric == "euclidean":
        pscore = x2qp[None, :] - 2.0 * pdots
    else:
        pscore = -pdots
    neg, sel = jax.lax.top_k(-pscore, width)            # [B, W]
    ids = probe_ids[sel]
    dist = -neg
    expanded = jnp.zeros((b, width), bool)
    rows_ix = jnp.arange(b)[:, None]

    def body(_i, state):
        ids, dist, expanded = state
        key = jnp.where(expanded, jnp.inf, dist)
        _v, esel = jax.lax.top_k(-key, expand)          # [B, E] best
        expanded = expanded.at[rows_ix, esel].set(True)
        src = jnp.take_along_axis(ids, esel, axis=1)    # [B, E]
        nb = graph[src].reshape(b, expand * d_out)      # [B, E*D]
        # drop already-present ids and intra-batch duplicates: a node
        # must enter the frontier once, already expanded state intact
        dup = (nb[:, :, None] == ids[:, None, :]).any(axis=2)
        inner = jnp.tril(
            nb[:, :, None] == nb[:, None, :], k=-1
        ).any(axis=2)
        nd = jnp.where(dup | inner, jnp.inf, score_rows(nb))
        mi = jnp.concatenate([ids, nb], axis=1)
        md = jnp.concatenate([dist, nd], axis=1)
        me = jnp.concatenate([expanded, dup | inner], axis=1)
        negk, keep = jax.lax.top_k(-md, width)
        ids = jnp.take_along_axis(mi, keep, axis=1)
        dist = -negk
        expanded = jnp.take_along_axis(me, keep, axis=1)
        return ids, dist, expanded

    ids, dist, _e = jax.lax.fori_loop(
        0, iters, body, (ids, dist, expanded)
    )
    neg, order = jax.lax.top_k(-dist, kc)
    return jnp.take_along_axis(ids, order, axis=1).astype(jnp.int32), -neg


def _descent_impl(graph, x8, arow, x2q, x8p, arowp, x2qp, probe_ids,
                  qs, metric, width, iters, expand, kc):
    ids, _dist = _descent_scored(graph, x8, arow, x2q, x8p, arowp, x2qp,
                                 probe_ids, qs, metric, width, iters,
                                 expand, kc)
    return ids


def _descent_jit(args, static, scored: bool = False):
    import jax

    from surrealdb_tpu.device.kernelstats import note_compile, note_hit

    n, dim, d_out, p, b = (
        args[1].shape[0], args[1].shape[1], args[0].shape[1],
        args[4].shape[0], args[8].shape[0],
    )
    ck = (n, dim, d_out, p, b, scored) + static
    fn = _jit_cache.get(ck)
    if fn is None:
        note_compile("ann_descent")
        fn = jax.jit(_descent_scored if scored else _descent_impl,
                     static_argnums=(9, 10, 11, 12, 13))
        _jit_cache[ck] = fn
    else:
        note_hit("ann_descent")
    return fn(*args, *static)


class AnnStore:
    """Device-resident quantized graph index for ONE build snapshot."""

    def __init__(self, key: str, graph: np.ndarray, x8: np.ndarray,
                 arow: np.ndarray, x2q: np.ndarray, metric: str,
                 cfg: dict):
        self.key = key
        self.graph = graph
        self.x8 = x8
        self.arow = arow
        self.x2q = x2q
        self.metric = metric
        self.cfg = dict(cfg)
        self.device = None

    def nbytes(self) -> int:
        return int(self.graph.nbytes + self.x8.nbytes
                   + self.arow.nbytes + self.x2q.nbytes)

    def device_nbytes(self) -> int:
        """Device-resident bytes once installed: the four shipped
        arrays plus the precomputed probe-row slices (`_ensure`, whose
        probe length IS probe_count — no array materialized here: this
        runs on every budget-admission pass). Used by the runner's
        byte budget (DeviceHost._admit)."""
        from surrealdb_tpu.idx.cagra import probe_count

        n, dim = self.x8.shape
        w = max(int(self.cfg.get("width", 64)), 1)
        return self.nbytes() + probe_count(n, w) * (dim + 12)

    @staticmethod
    def estimate_device_bytes(n: int, dim: int, d_out: int) -> int:
        """Admission estimate from the begin-frame shapes (before the
        staging buffers are allocated): graph int32 + x8 rows + the
        f32 per-row arrays; probe slices add at most ~N/24 rows."""
        n = max(int(n), 0)
        probe = min(n, max(4096, n // 8))
        return n * (4 * max(int(d_out), 1) + max(int(dim), 1) + 8) \
            + probe * (max(int(dim), 1) + 12)

    def _ensure(self):
        if self.device is None:
            import jax.numpy as jnp

            from surrealdb_tpu.idx.cagra import entry_ids, probe_count

            n = self.x8.shape[0]
            w = max(int(self.cfg.get("width", 64)), 1)
            probe = entry_ids(n, probe_count(n, w))
            self.device = (
                jnp.asarray(self.graph),
                jnp.asarray(self.x8),
                jnp.asarray(self.arow),
                jnp.asarray(self.x2q),
                # probe rows precomputed: the seed stage is a [B, P]
                # gemm, never a [B, P, D] gather
                jnp.asarray(self.x8[probe]),
                jnp.asarray(self.arow[probe]),
                jnp.asarray(self.x2q[probe]),
                jnp.asarray(probe.astype(np.int32)),
            )
        return self.device

    def search(self, qs: np.ndarray, kc: int) -> np.ndarray:
        """[B, D] f32 queries -> [B, kc] int32 candidate ids (unique
        per row, best-first by int8 descent score). Batch sizes round
        up to a power of two so compiled shapes stay a bounded ladder."""
        import jax.numpy as jnp

        from surrealdb_tpu.device.kernelstats import ANN, note_shape, phase

        dev = self._ensure()
        n = self.x8.shape[0]
        p = int(dev[7].shape[0])  # probe rows precomputed at install
        cfg = self.cfg
        width = max(int(cfg.get("width", 64)), 1)
        iters = max(int(cfg.get("iters", 24)), 1)
        expand = max(int(cfg.get("expand", 2)), 1)
        kc = min(max(int(kc), 1), n)
        # the frontier seeds from the probe's top-`width`, so width is
        # bounded by the probe size fixed at install (an oversized kc —
        # huge oversample × k — clamps down rather than raising inside
        # top_k; the serving side treats the returned column count as
        # the candidate budget)
        width = min(max(width, kc), n, p)
        kc = min(kc, width)
        expand = min(expand, width)
        b = qs.shape[0]
        bucket = 1
        while bucket < b:
            bucket *= 2
        qsb = np.ascontiguousarray(qs, np.float32)
        if bucket != b:
            qsb = np.concatenate(
                [qsb, np.zeros((bucket - b, qsb.shape[1]), np.float32)]
            )
        static = (self.metric, width, iters, expand, kc)
        note_shape("ann_descent", (self.x8.shape, self.graph.shape[1],
                                   bucket) + static)
        # the op's timeline, as in VecStore.knn (kernelstats.phase): the
        # one output's arrival on the host ends `device`
        with phase("h2d"):
            qsd = jnp.asarray(qsb)
        with phase("device"):
            cand = np.asarray(_descent_jit(dev + (qsd,), static))
        with phase("d2h"):
            out = np.ascontiguousarray(cand[:b], np.int32)
        # the loop is a fixed fori_loop over static shapes, so the rows
        # a descent scores are known here, from shapes: every iteration
        # scores expand*d_out neighbours a rider, after one probe of p
        # rows a search. Counted for the riders that asked, not the
        # bucket they were padded to.
        ANN["searches"] += 1
        ANN["rows_scored"] += \
            b * iters * expand * int(self.graph.shape[1]) + p
        return out
