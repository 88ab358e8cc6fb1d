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

The single-device bf16 ranking store GROWS IN PLACE: its arrays are
allocated at `capacity_for(n)` rows, the rows past `n` are invalid by
the mask the kernels already honour, every program is keyed by the
capacity and never by `n`, and `append` writes new, overwritten and
tombstoned rows at their row numbers with one donating program
(`vec_append`). A whole re-ship, and new programs, come once a
capacity step of rows. The other branches (exact, int8, a mesh) keep
the whole-load path.
"""

from __future__ import annotations

import numpy as np


MXU_METRICS = ("euclidean", "cosine", "dot")


def exact_store(metric: str, cfg: dict) -> bool:
    """Whether a store keeps its f32 rows alone and answers from the
    exact kernels: always for the non-MXU metrics, and for any metric
    when the shipper's `cfg` says `exact`."""
    return metric not in MXU_METRICS or bool(cfg.get("exact"))


def capacity_for(n: int) -> int:
    """Rows a growing store of `n` rows is allocated for: a function of
    `n` alone. The step is a thirty-second of the next power of two
    (256 at least: 4,096 at 100,000 rows), and the headroom is one to
    two steps, 3-9 % of the rows from 8,192 up: (n // step + 2) * step."""
    n = max(int(n), 1)
    step = max(256, (1 << (n - 1).bit_length()) // 32)
    return (n // step + 2) * step


def _append_bucket(m: int) -> int:
    """The ladder of delta sizes `append` pads to: 1, 2, 4, ..."""
    return 1 << max(int(m) - 1, 0).bit_length()


# lint: mem-account(one jitted function a metric: three at most)
_APPEND_PROGRAMS: dict = {}


def _append_program(metric: str):
    """The jitted, donating program behind `VecStore.append`, one a
    metric (the program's name in a device trace is `jit_vec_append`
    whatever the metric: `benchmark/layers/vec_append_roofline.py`
    finds it there). `packed` is ONE int32 array [m, D + 3]: the new
    rows' f32 bits, each row's stat's f32 bits (x2 under euclidean, the
    norm under cosine), its row number and its mask bit. A row number
    outside the store (the padding of a delta to its ladder step) is
    dropped by the scatter."""
    fn = _APPEND_PROGRAMS.get(metric)
    if fn is not None:
        return fn
    import jax
    import jax.numpy as jnp

    def vec_append(full, rank, stat, valid, packed):
        with jax.named_scope("vec_append"):
            dim = full.shape[1]
            rows = jax.lax.bitcast_convert_type(
                packed[:, :dim], jnp.float32)
            rstat = jax.lax.bitcast_convert_type(
                packed[:, dim], jnp.float32)
            idx = packed[:, dim + 1]
            flags = packed[:, dim + 2] != 0
            # as `ensure` derives the ranking copy from the f32 rows
            if metric == "cosine":
                rrows = (rows / rstat[:, None]).astype(jnp.bfloat16)
            else:
                rrows = rows.astype(jnp.bfloat16)
            return (full.at[idx].set(rows, mode="drop"),
                    rank.at[idx].set(rrows, mode="drop"),
                    stat.at[idx].set(rstat, mode="drop"),
                    valid.at[idx].set(flags, mode="drop"))

    fn = jax.jit(vec_append, donate_argnums=(0, 1, 2, 3))
    _APPEND_PROGRAMS[metric] = fn
    return fn


def row_stat(rows: np.ndarray, metric: str):
    """The per-row stat of the ranking stores, f64 on the host: squared
    norms for euclidean ranking, norms for the cosine rescore, None for
    dot. `ensure` computes it for a whole block and `append` for a
    delta: a row's stat does not depend on its neighbours, so an
    appended row is bit-identical to the same row loaded fresh."""
    if metric == "euclidean":
        return (rows.astype(np.float64) ** 2).sum(axis=1).astype(np.float32)
    if metric == "cosine":
        return np.maximum(
            np.linalg.norm(rows.astype(np.float64), axis=1), 1e-30
        ).astype(np.float32)
    return None


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
        # the shipped rows; the growing store lets go of them once
        # they are on the device (`ensure`): nothing reads them there,
        # and an append would leave them behind
        self.vecs = vecs
        self.valid = valid.astype(bool)
        self.n, self.dim = (int(v) for v in vecs.shape)
        self.itemsize = int(vecs.dtype.itemsize)
        # rows the device arrays are allocated for: `n`, or, where the
        # store grows in place, `capacity_for(n)` as loaded
        self.capacity = self.n
        self.growable = False
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

    @property
    def shape(self) -> tuple:
        """(rows, dim) as of the last load or append."""
        return (self.n, self.dim)

    def nbytes(self) -> int:
        return self.n * self.dim * self.itemsize

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
        # one device: the bf16 store is allocated, and budgeted, at
        # its capacity (`ensure` tests the same product)
        rows = capacity_for(n) if ndev == 1 else n
        if (6 * rows * dim) // max(ndev, 1) > cfg.get("hbm_budget",
                                                      1 << 62):
            # int8 ranking store: rows (1 B/elem) + arow/x2 + valid
            return n * dim + 9 * n
        # bf16 rank + f32 full (6 B/elem) + per-row stats + valid
        return (6 * rows * dim) // max(ndev, 1) + 9 * rows

    def device_nbytes(self) -> int:
        """Estimated device-resident bytes for the budget ledger (the
        host mirror in `self.vecs` is serving-process memory, already
        accounted there)."""
        if self.growable:
            # what is allocated, whatever `n` has grown to since
            return 6 * self.capacity * self.dim + 9 * self.capacity
        return self.estimate_device_bytes(
            self.n, self.dim, self.itemsize, self.metric, self.cfg
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
            x2 = row_stat(xs, self.metric)
        elif self.metric == "cosine":
            norms = row_stat(xs, self.metric)
        n, dim = xs.shape
        ndev = jax.device_count()
        cap = n if multi else capacity_for(n)
        if (6 * cap * dim) // max(ndev, 1) > self.cfg["hbm_budget"]:
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
            # the store that grows in place: every array at `cap` rows,
            # padded HERE on the host (one transfer each, and no eager
            # program specialised on `n`); the rows past `n` are
            # invalid. One stat array whatever the metric, so that
            # `append` is one program: x2 (zeros under dot), or norms
            # (ones past `n`: the ranking copy divides by them).
            def padded(a, fill=0):
                out = np.full((cap,) + a.shape[1:], fill, a.dtype)
                out[:n] = a
                return out

            self.device_full = jnp.asarray(
                padded(np.asarray(xs, np.float32)))
            if norms is not None:
                self.device_norms = jnp.asarray(padded(norms, 1.0))
            else:
                self.device_x2 = jnp.asarray(padded(
                    x2 if x2 is not None else np.zeros(n, np.float32)))
            self.device_valid = jnp.asarray(padded(valid))
            self.capacity = cap
            self.growable = True
            self.vecs = self.valid = None
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
        n = self.n
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
                           (self.shape, chunk, k, kc, self.metric))
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
                           (self.shape, b_total, k, self.metric))
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
                       (self.shape, chunk, kc, self.metric))
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
            # tombstoned rows ranked into the candidate set. The
            # program is keyed by the CAPACITY the arrays have, never
            # by `n`: a store that grows keeps its programs (a slot
            # the mask rules out comes back as inf and is dropped by
            # the serving side, as a tombstone's is)
            cap = self.capacity
            kc = min(cap, max(2 * k, k + 16))
            bucket, chunk, _ = _pow2_chunks(
                b_total, cap, cfg["query_chunk"], cfg["score_budget"]
            )
            note_shape("knn_rank_rescore",
                       ((cap, self.dim), chunk, min(k, kc), kc,
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
                       (self.shape, chunk, k, self.metric))
            qs_r = _chunked(qvs, bucket, chunk)
            with phase("device"):
                parts = [np.asarray(topk.exact_scan(
                    self.device_vecs, qs_r[r], k, self.metric,
                    self.mink_p, self.device_valid, cfg["block_rows"],
                )) for r in range(rounds)]
            packed = parts[0] if rounds == 1 else np.concatenate(parts)
        return self._pairs(*topk.unpack_pairs(packed[:b_total]))

    def append(self, rows: np.ndarray, row_numbers: np.ndarray,
               flags: np.ndarray) -> bool:
        """Writes a delta into the resident block, in place: `rows`
        [m, D] at `row_numbers` [m] (distinct; new rows past `n`,
        overwritten and tombstoned rows below it) with mask bits
        `flags` [m]. One transfer in (the delta packed into one int32
        array, padded to its ladder step), one donating program, and
        nothing copied back: the next search's program waits for it on
        the device. False, and nothing written, where the store does
        not grow in place or a row number lies outside its capacity:
        the caller ships the whole block again."""
        if not self.growable:
            return False
        m = int(len(row_numbers))
        top = int(row_numbers.max()) + 1 if m else 0
        if top > self.capacity or (m and int(row_numbers.min()) < 0):
            return False
        # the stat from the rows as shipped (an f64 index's too), as
        # `ensure` takes it; then the f32 rows the device keeps
        rows = np.asarray(rows).reshape(m, self.dim)
        stat = row_stat(rows, self.metric)
        rows = np.ascontiguousarray(rows, np.float32)
        packed = self._padding(m)
        packed[:m, :self.dim] = rows.view(np.int32)
        if stat is not None:
            packed[:m, self.dim] = stat.view(np.int32)
        packed[:m, self.dim + 1] = row_numbers
        packed[:m, self.dim + 2] = np.asarray(flags, bool)
        self._run_append(packed)
        self.n = max(self.n, top)
        return True

    def warm_append(self, rows: int):
        """Compiles (or loads) the append program of the ladder step
        that holds `rows` rows, by a delta that is all padding."""
        if self.growable:
            self._run_append(self._padding(rows))

    def _padding(self, m: int) -> np.ndarray:
        """The packed delta of the ladder step that holds `m` rows,
        every row number outside the store: the scatter drops them."""
        packed = np.zeros((_append_bucket(m), self.dim + 3), np.int32)
        packed[:, self.dim + 1] = self.capacity
        return packed

    def _run_append(self, packed: np.ndarray):
        from surrealdb_tpu.device.kernelstats import note_shape, phase

        cosine = self.metric == "cosine"
        note_shape("vec_append", ((self.capacity, self.dim),
                                  packed.shape[0], self.metric))
        with phase("device"):
            out = _append_program(self.metric)(
                self.device_full, self.device_rank,
                self.device_norms if cosine else self.device_x2,
                self.device_valid, packed)
        self.device_full, self.device_rank, stat_dev, \
            self.device_valid = out
        if cosine:
            self.device_norms = stat_dev
        else:
            self.device_x2 = stat_dev

    def _pairs(self, dists, ids):
        return (
            {"mode": "pairs", "rank_mode": self.rank_mode},
            [
                np.ascontiguousarray(dists, np.float32),
                np.ascontiguousarray(ids, np.int32),
            ],
        )
