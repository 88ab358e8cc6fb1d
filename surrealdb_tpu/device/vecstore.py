"""Runner-side vector block store: the JAX/TPU half of TpuVectorIndex.

Everything here runs inside the DeviceRunner subprocess (or, in
`SURREAL_DEVICE=inline` debug/test mode, in-process). The serving
process ships raw `[N, D]` rows + validity mask once per cache epoch;
queries arrive as `[B, D]` f32 batches and leave as `[B, k]`
(dist, row-id) tiles — RecordId mapping and the int8 path's exact host
rescore stay on the serving side, which holds the full-precision rows.

The kernel selection mirrors the pre-supervisor design exactly
(bf16 rank + f32 rescore single-chip, sharded rank/rescore on a mesh,
int8 ranking store above the HBM budget, exact kernels for non-MXU
metrics); budgets arrive in `cfg` per dispatch so the serving process's
configuration governs. A store whose `cfg` says `exact` (a table's
column block under a no-index scan, col.py) takes the exact kernels
whatever its metric: f32 rows only, every row scored, no candidate set.
"""

from __future__ import annotations

import numpy as np


MXU_METRICS = ("euclidean", "cosine", "dot")


def exact_store(metric: str, cfg: dict) -> bool:
    """Whether a store keeps its f32 rows alone and answers from the
    exact kernels: always for the non-MXU metrics, and for any metric
    when the shipper's `cfg` says `exact`."""
    return metric not in MXU_METRICS or bool(cfg.get("exact"))


def _device_count() -> int:
    """Real device count when jax is up (it always is runner-side —
    init precedes serving; inline mode imports it on first ensure),
    else 1. Kept lazy so constructing a store never triggers init."""
    import sys

    jax = sys.modules.get("jax")
    if jax is None:
        return 1
    try:
        return max(jax.device_count(), 1)
    except Exception:
        return 1


def _pow2_chunks(b_total: int, n: int, query_chunk: int,
                 elems_budget: int):
    """Power-of-two query bucket/chunk sizing shared by every ranking
    branch: a bounded set of compiled kernel shapes under dynamic batch
    sizes, with the [chunk, n] score matrix held under `elems_budget`
    elements. Returns (bucket, chunk, rounds)."""
    cap = min(max(1, query_chunk), max(1, elems_budget // max(n, 1)))
    bucket = 1
    while bucket < b_total:
        bucket *= 2
    chunk = 1
    while chunk * 2 <= min(cap, bucket):
        chunk *= 2
    return bucket, chunk, bucket // chunk


def _chunked(qvs: np.ndarray, bucket: int, chunk: int) -> np.ndarray:
    """[B, D] queries zero-padded to `bucket` rows and cut into
    [bucket // chunk, chunk, D], on the host: the copy is at most a
    query chunk of floats and the reshape is a view."""
    if bucket != qvs.shape[0]:
        padded = np.zeros((bucket, qvs.shape[1]), qvs.dtype)
        padded[:qvs.shape[0]] = qvs
        qvs = padded
    return qvs.reshape(bucket // chunk, chunk, -1)


class VecStore:
    """Device-resident blocks for ONE vector index cache epoch."""

    def __init__(self, key: str, vecs: np.ndarray, valid: np.ndarray,
                 metric: str, mink_p: float, cfg: dict):
        self.key = key
        self.vecs = vecs
        self.valid = valid.astype(bool)
        self.metric = metric
        self.mink_p = float(mink_p)
        self.cfg = dict(cfg)
        self.device_vecs = None
        self.device_valid = None
        self.device_rank = None
        self.device_full = None
        self.device_norms = None
        self.device_x2 = None
        self.device_arow = None
        self.rank_mode = None  # "bf16" | "int8" | None (exact store)
        self.mesh = None

    def nbytes(self) -> int:
        return int(self.vecs.nbytes)

    @staticmethod
    def estimate_device_bytes(n: int, dim: int, itemsize: int,
                              metric: str, cfg: dict,
                              ndev: int = 0) -> int:
        """Device-resident bytes this store will pin once ensured —
        mirrors `ensure()`'s kernel-selection branches (including the
        per-chip HBM share that picks bf16-vs-int8) so the runner's
        byte budget can ADMIT OR REFUSE a ship before allocating
        anything (DeviceHost._admit). `ndev` 0 resolves the real
        device count — passing 1 on a mesh would both pick the wrong
        kernel branch and overstate the per-chip share."""
        if ndev <= 0:
            ndev = _device_count()
        n = max(int(n), 0)
        dim = max(int(dim), 1)
        if exact_store(metric, cfg):
            # exact store: the raw rows + the validity mask
            return (n * dim * itemsize) // max(ndev, 1) + n
        if (6 * n * dim) // max(ndev, 1) > cfg.get("hbm_budget",
                                                   1 << 62):
            # int8 ranking store: rows (1 B/elem) + arow/x2 + valid
            return n * dim + 9 * n
        # bf16 rank + f32 full (6 B/elem) + per-row stats + valid
        return (6 * n * dim) // max(ndev, 1) + 9 * n

    def device_nbytes(self) -> int:
        """Estimated device-resident bytes for the budget ledger (the
        host mirror in `self.vecs` is serving-process memory, already
        accounted there)."""
        n, dim = self.vecs.shape
        return self.estimate_device_bytes(
            n, dim, self.vecs.dtype.itemsize, self.metric, self.cfg
        )

    def ensure(self):
        if self.device_vecs is not None or self.device_rank is not None:
            return
        import jax
        import jax.numpy as jnp

        valid = self.valid.copy()
        multi = jax.device_count() > 1
        if exact_store(self.metric, self.cfg):
            # non-MXU metrics and stores told to be exact: the exact
            # distance kernel over the raw store, and no ranking copy
            if multi:
                from surrealdb_tpu.parallel.mesh import (
                    default_mesh, shard_rows, shard_vec,
                )

                self.mesh = default_mesh()
                self.device_vecs, pad = shard_rows(self.mesh, self.vecs)
                self.device_valid = shard_vec(self.mesh, valid, pad)
            else:
                self.device_vecs = jnp.asarray(self.vecs)
                self.device_valid = jnp.asarray(valid)
            return
        # MXU metrics, single- and multi-chip alike: f32 full store is
        # the ONE host→device transfer; the bf16 ranking store and
        # cosine's pre-normalized rows are derived from it ON DEVICE.
        # Per-row stats (x2 for euclidean ranking, norms for cosine
        # rescore) are f64-accurate host computations.
        xs = self.vecs
        self.device_norms = None
        self.device_x2 = None
        x2 = norms = None
        if self.metric == "euclidean":
            x2 = (xs.astype(np.float64) ** 2).sum(axis=1).astype(np.float32)
        elif self.metric == "cosine":
            norms = np.maximum(
                np.linalg.norm(xs.astype(np.float64), axis=1), 1e-30
            ).astype(np.float32)
        n, dim = xs.shape
        ndev = jax.device_count()
        if (6 * n * dim) // max(ndev, 1) > self.cfg["hbm_budget"]:
            # bf16 rank + f32 full (6 B/elem, per-chip share under a
            # mesh) won't fit HBM: int8 ranking store (1 B/elem); the
            # EXACT rescore of the oversampled candidates happens on the
            # serving side from its full-precision rows.
            x8 = np.empty((n, dim), np.int8)
            arow = np.empty(n, np.float32)
            step = max(1, (256 << 20) // max(dim * 4, 1))
            for s in range(0, n, step):
                blk = xs[s:s + step].astype(np.float32)
                if self.metric == "cosine":
                    blk = blk / norms[s:s + step, None]
                m = np.maximum(np.abs(blk).max(axis=1), 1e-30)
                x8[s:s + step] = np.rint(
                    blk * (127.0 / m)[:, None]
                ).astype(np.int8)
                arow[s:s + step] = m / 127.0
            self.device_rank = jnp.asarray(x8)
            self.device_arow = jnp.asarray(arow)
            self.device_x2 = jnp.asarray(
                x2 if x2 is not None else np.zeros(n, np.float32)
            )
            self.device_valid = jnp.asarray(valid)
            self.rank_mode = "int8"
            return
        if multi:
            from surrealdb_tpu.parallel.mesh import (
                default_mesh, shard_rows, shard_vec,
            )

            self.mesh = default_mesh()
            self.device_full, pad = shard_rows(
                self.mesh, xs.astype(np.float32)
            )
            # always materialize both stats (zeros/ones when the metric
            # doesn't use one): sharded defaults built per-query inside
            # sharded_rank_rescore would eagerly allocate [N] per call
            self.device_x2 = shard_vec(
                self.mesh,
                x2 if x2 is not None else np.zeros(n, np.float32), pad,
            )
            self.device_norms = shard_vec(
                self.mesh,
                norms if norms is not None else np.ones(n, np.float32),
                pad, 1.0,
            )
            self.device_valid = shard_vec(self.mesh, valid, pad)
        else:
            self.device_full = jnp.asarray(xs, dtype=jnp.float32)
            if x2 is not None:
                self.device_x2 = jnp.asarray(x2)
            if norms is not None:
                self.device_norms = jnp.asarray(norms)
            self.device_valid = jnp.asarray(valid)
        if self.metric == "cosine":
            self.device_rank = (
                self.device_full / self.device_norms[:, None]
            ).astype(jnp.bfloat16)
        else:
            self.device_rank = self.device_full.astype(jnp.bfloat16)
        self.rank_mode = "bf16"

    def knn(self, qvs: np.ndarray, k: int):
        """Batched device search: [B, D] f32 queries -> (meta, bufs).

        mode "pairs": bufs = [dists f32 [B, k'], ids i32 [B, k']] —
        final results (invalid slots carry inf / out-of-range ids).
        mode "cand": bufs = [cand i32 [B, kc]] — int8 ranking
        candidates for the serving side's exact host rescore."""
        self.ensure()
        from surrealdb_tpu.device.kernelstats import note_shape, phase

        # On one device a dispatch is one transfer in, one program and
        # one copy out: the batch is padded to its bucket and cut into
        # chunks HERE, in numpy (on a device array either is an eager
        # program of its own, compiled per rider count), the jitted
        # kernel takes the numpy batch and its dispatch places it, and
        # the kernel packs its outputs into one array (ops.topk
        # pack_pairs), because every copy of a small ready array back to
        # the host is a round trip to the chip. The op's timeline
        # (kernelstats.phase) is `device` alone, from the launch until
        # that array is on the host: the wait that was always there (a
        # block_until_ready before it costs one more round trip, 0.46 ms
        # a query on a v5e). The mesh branches keep `h2d` / `d2h` where
        # they still transfer or copy apart.
        cfg = self.cfg
        n = self.vecs.shape[0]
        qvs = np.ascontiguousarray(qvs, dtype=np.float32)
        b_total = qvs.shape[0]
        if self.mesh is not None:
            if self.device_rank is not None:
                from surrealdb_tpu.parallel.mesh import sharded_rank_rescore

                kc = max(2 * k, k + 16)
                nloc = self.device_rank.shape[0] // self.mesh.devices.size
                _, chunk, _ = _pow2_chunks(
                    b_total, nloc, cfg["query_chunk"], cfg["score_budget"]
                )
                note_shape("sharded_rank_rescore",
                           (self.vecs.shape, chunk, k, kc, self.metric))
                d_parts = []
                i_parts = []
                for s in range(0, b_total, chunk):
                    qc = qvs[s:s + chunk]
                    if qc.shape[0] < chunk:
                        qc = np.pad(qc, ((0, chunk - qc.shape[0]), (0, 0)))
                    with phase("device"):
                        dc, ic = sharded_rank_rescore(
                            self.mesh, self.device_rank, self.device_full,
                            qc, k, kc, self.metric, self.device_x2,
                            self.device_norms, self.device_valid,
                        )
                        d_parts.append(np.asarray(dc))
                    with phase("d2h"):
                        i_parts.append(np.asarray(ic))
                dists = np.concatenate(d_parts)[:b_total]
                ids = np.concatenate(i_parts)[:b_total]
            else:
                import jax.numpy as jnp

                from surrealdb_tpu.parallel.mesh import sharded_knn

                note_shape("sharded_knn",
                           (self.vecs.shape, b_total, k, self.metric))
                with phase("h2d"):
                    qs = jnp.asarray(qvs)
                with phase("device"):
                    dists, ids = sharded_knn(
                        self.mesh, self.device_vecs, qs, self.device_valid,
                        k, self.metric, self.mink_p,
                    )
                    dists = np.asarray(dists)
                with phase("d2h"):
                    ids = np.asarray(ids)
            return self._pairs(dists, ids)
        # looked up on the module at every call: the benchmark's fault
        # hook plants its `knn_rank_rescore` there
        from surrealdb_tpu.ops import topk

        if self.rank_mode == "int8":
            kc = min(n, max(cfg["int8_oversample"] * k, k + 16))
            # halve the score budget: the int8 kernel holds int32 dots
            # AND the f32 score matrix at [chunk, N] concurrently
            bucket, chunk, _ = _pow2_chunks(
                b_total, n, cfg["query_chunk"], cfg["score_budget"] // 2
            )
            note_shape("knn_rank_int8",
                       (self.vecs.shape, chunk, kc, self.metric))
            qs_r = _chunked(qvs, bucket, chunk)
            with phase("device"):
                cand = np.asarray(topk.knn_rank_int8(
                    self.device_rank, self.device_arow, self.device_x2,
                    self.device_valid, qs_r, kc, self.metric,
                ))
            return (
                {"mode": "cand", "rank_mode": self.rank_mode, "kc": kc},
                [np.ascontiguousarray(
                    cand.reshape(bucket, kc)[:b_total], np.int32)],
            )
        if self.device_rank is not None:
            # oversampling absorbs bf16/approx-top-k ranking error AND
            # tombstoned rows ranked into the candidate set
            kc = min(n, max(2 * k, k + 16))
            bucket, chunk, _ = _pow2_chunks(
                b_total, n, cfg["query_chunk"], cfg["score_budget"]
            )
            note_shape("knn_rank_rescore",
                       (self.vecs.shape, chunk, min(k, kc), kc,
                        self.metric))
            qs_r = _chunked(qvs, bucket, chunk)
            with phase("device"):
                packed = np.asarray(topk.knn_rank_rescore(
                    self.device_rank, self.device_full, qs_r,
                    min(k, kc), kc, self.metric, self.device_x2,
                    self.device_norms, self.device_valid,
                ))
            packed = packed.reshape(bucket, -1)
        else:
            # the exact store: the batch padded to its bucket like the
            # ranking branches' (a program a bucket, not a rider count)
            blocked = n > cfg["block_rows"]
            bucket, chunk, rounds = _pow2_chunks(
                b_total, min(n, topk.SCAN_BLOCK) if blocked else n,
                cfg["query_chunk"], cfg["score_budget"]
            )
            note_shape("exact_scan",
                       (self.vecs.shape, chunk, k, self.metric))
            qs_r = _chunked(qvs, bucket, chunk)
            with phase("device"):
                parts = [np.asarray(topk.exact_scan(
                    self.device_vecs, qs_r[r], k, self.metric,
                    self.mink_p, self.device_valid, cfg["block_rows"],
                )) for r in range(rounds)]
            packed = parts[0] if rounds == 1 else np.concatenate(parts)
        return self._pairs(*topk.unpack_pairs(packed[:b_total]))

    def _pairs(self, dists, ids):
        return (
            {"mode": "pairs", "rank_mode": self.rank_mode},
            [
                np.ascontiguousarray(dists, np.float32),
                np.ascontiguousarray(ids, np.int32),
            ],
        )
