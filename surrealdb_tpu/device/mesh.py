"""Mesh execution layer: row-shard vec/ANN/CSR blocks across devices.

The paper's north star — "batched distance + `jax.lax.top_k` + `psum`
over an ICI mesh" — as a DeviceRunner subsystem: at install time the
runner cuts a shipped block table into contiguous row (vec/ANN) or
edge (CSR) slices, one per device of a 1-D mesh; each query runs the
per-device partial kernel (brute distance, int8 descent scoring, CSR
hop expansion) with a device-local `top_k`, then merges ON-MESH — one
`all_gather` of the [B, k_local] (dist, global-id) partials followed by
a final exact `top_k` (scatter-add + `psum` for CSR). The merge is the
same contract as idx/shardvec.merge_topk (ascending distance, ties to
the lower global id), so sharded answers are byte-identical to a
single-device run of the same kernel:

- brute/exact and int8 ranking scores are per-(row, query) — row-
  independent — so per-shard scores equal the single-device scores
  bitwise, and the concatenation order of the gathered partials
  (ascending shard base) makes positional tie-breaking equal global-id
  tie-breaking, i.e. exactly `lax.top_k` over the whole store;
- CSR hop counts are integer scatter-adds — associative — so partial
  per-device sums + `psum` reproduce the single-device frontier
  exactly;
- graph descent is partitioned (per-device sub-graph over the local
  rows; foreign edges become self-loops the dup mask kills; per-slice
  routing probes), so the mesh result is byte-identical to a
  SEQUENTIAL run of the same partitioned structure (`search_seq`) —
  the oracle the property suite checks — not to a 1-device descent
  over a different (whole-store) graph.

Placement is budget-aware: `pick_ndev` walks the pow2 ladder and picks
the smallest mesh whose PER-DEVICE share of the install estimate fits
`DeviceHost.budget_bytes` — a store that fits on 8 devices but not 1
shards instead of refusing (spill-to-host unchanged).

Importing this module never touches jax (placement math is pure
Python); the stores import jax lazily like vecstore/annstore, so
serving-process code may import it for the knobs. Testable today on
CPU: `XLA_FLAGS=--xla_force_host_platform_device_count=8
python -m surrealdb_tpu.device.mesh --devices 8 --budget-check`.
"""

from __future__ import annotations

import os

import numpy as np

from surrealdb_tpu import cnf
from surrealdb_tpu.device.vecstore import exact_store

MESH_AXIS = "mesh"

# one jitted shard_map per (kernel, mesh, shapes, statics) — the same
# bounded compiled-ladder discipline as csrstore._jit_cache
_jit_cache: dict = {}  # robust: mem-account (bounded: pow2 shape ladder per resident store, cleared with the runner process)


# -- topology / placement knobs ------------------------------------------


def mesh_mode() -> str:
    """SURREAL_DEVICE_MESH: "auto" (shard when >1 device), "off",
    "force" (shard even when placement says 1 fits), or an integer cap.
    Read from the environment per call so tests/bench can flip it
    without reloading cnf."""
    raw = os.environ.get("SURREAL_DEVICE_MESH")
    if raw is None:
        raw = getattr(cnf, "DEVICE_MESH", "auto")
    raw = str(raw).strip().lower()
    return raw or "auto"


def _mesh_cap() -> int:
    mode = mesh_mode()
    if mode in ("auto", "force"):
        return 0  # uncapped
    if mode == "off":
        return 1
    try:
        return max(int(mode), 1)
    except ValueError:
        return 0


def mesh_size() -> int:
    """Usable mesh width: the runner's device count under the
    SURREAL_DEVICE_MESH cap; 1 when the mesh is off or jax is not up
    (kept lazy exactly like vecstore._device_count so calling this
    never triggers backend init in the serving process)."""
    if mesh_mode() == "off":
        return 1
    import sys

    jax = sys.modules.get("jax")
    if jax is None:
        return 1
    try:
        n = max(int(jax.device_count()), 1)
    except Exception:
        return 1
    cap = _mesh_cap()
    return min(n, cap) if cap else n


def describe() -> dict:
    """Topology snapshot for the runner ready-frame / status()."""
    n = mesh_size()
    return {"mode": mesh_mode(), "n_devices": n, "mesh_shape": [n],
            "axis": MESH_AXIS}


def pick_ndev(est_total_fn, budget_bytes: int, n_rows: int = 1 << 62) -> int:
    """Device count for a new store install. `est_total_fn(d)` returns
    the estimated TOTAL device bytes when sharded over `d` devices
    (padding included); the chosen count is the smallest pow2 whose
    per-device share `ceil(est/d)` fits the per-device budget — the
    "fits on 8 but not 1 → shard" rule. "force" mode → the full mesh;
    no budget under "auto" → 1 (nothing to rescue: the legacy stores
    keep their own self-sharded rank paths). Clamped to `n_rows` so
    no slice is ever empty. Over budget even fully sharded → the full
    mesh; `_admit` then refuses honestly."""
    nmesh = min(mesh_size(), max(int(n_rows), 1))
    if nmesh <= 1:
        return 1
    if mesh_mode() == "force":
        return nmesh
    if budget_bytes <= 0:
        return 1
    cands = []
    d = 1
    while d < nmesh:
        cands.append(d)
        d *= 2
    cands.append(nmesh)
    for d in cands:
        if -(-int(est_total_fn(d)) // d) <= budget_bytes:
            return d
    return nmesh


def even_splits(n: int, ndev: int) -> list:
    """Contiguous shard fenceposts [0, ..., n] (ndev+1 entries)."""
    ndev = max(int(ndev), 1)
    step = -(-n // ndev) if n else 0
    return [min(i * step, n) for i in range(ndev + 1)]


def _check_offsets(offs, n: int, ndev: int, allow_empty: bool = True):
    if len(offs) != ndev + 1 or offs[0] != 0 or offs[-1] != n:
        raise ValueError(f"bad mesh offsets {offs!r} for n={n} ndev={ndev}")
    for a, b in zip(offs, offs[1:]):
        if b < a or (not allow_empty and b == a):
            raise ValueError(f"bad mesh offsets {offs!r}: "
                             f"{'empty' if b == a else 'unordered'} slice")


def _pack(a: np.ndarray, offs, nloc: int, fill=0) -> np.ndarray:
    """Slice `a` at `offs` and pad every slice to `nloc` rows, laid out
    contiguously [ndev*nloc, ...] so P(MESH_AXIS, ...) puts slice s on
    device s."""
    ndev = len(offs) - 1
    out = np.full((ndev * nloc,) + a.shape[1:], fill, a.dtype)
    for s in range(ndev):
        ln = offs[s + 1] - offs[s]
        out[s * nloc:s * nloc + ln] = a[offs[s]:offs[s + 1]]
    return out


def _make_mesh(devices):
    """1-D device mesh over `devices` along MESH_AXIS."""
    from jax.sharding import Mesh

    return Mesh(np.asarray(devices), (MESH_AXIS,))


def _jit_entry(name: str, key, build):
    """csrstore-style compile accounting around the shard_map cache."""
    from surrealdb_tpu.device.kernelstats import note_compile, note_hit

    fn = _jit_cache.get(key)
    if fn is None:
        note_compile(name)
        fn = build()
        _jit_cache[key] = fn
    else:
        note_hit(name)
    return fn


# -- sharded vector store ------------------------------------------------


def _vec_exact_jit(mesh, dim, nloc, chunk, k_l, k_out, metric, p, n):
    def build():
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from surrealdb_tpu.ops.distance import distance_matrix

        def shard(xs, valid, base, qs):
            d = distance_matrix(xs, qs, metric, p)
            d = jnp.where(valid[None, :], d, jnp.inf)
            neg, loc = jax.lax.top_k(-d, k_l)
            # globalize then clamp: a padding row surfacing at +inf
            # (k > live rows) must not index past the store
            gid = jnp.minimum(loc + base[0], n - 1).astype(jnp.int32)
            d_all = jax.lax.all_gather(-neg, MESH_AXIS, axis=1, tiled=True)
            i_all = jax.lax.all_gather(gid, MESH_AXIS, axis=1, tiled=True)
            neg2, sel = jax.lax.top_k(-d_all, k_out)
            return -neg2, jnp.take_along_axis(i_all, sel, axis=1)

        return jax.jit(jax.shard_map(
            shard, mesh=mesh,
            in_specs=(P(MESH_AXIS, None), P(MESH_AXIS),
                      P(MESH_AXIS), P(None, None)),
            out_specs=(P(None, None), P(None, None)),
            check_vma=False,
        ))

    key = ("vec_exact", mesh, dim, nloc, chunk, k_l, k_out, metric, p)
    return _jit_entry("mesh_vec_exact", key, build)


def _vec_int8_jit(mesh, dim, nloc, chunk, kc_l, kc_out, metric, n):
    def build():
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        def shard(x8, arow, x2, valid, base, qs):
            # knn_rank_int8's scoring recipe verbatim — per-row quant is
            # row-independent, so per-shard scores == single-device
            # scores bitwise; only the top-k selection is partitioned
            sq = 127.0 / jnp.maximum(jnp.abs(qs).max(axis=1), 1e-30)
            q8 = jnp.round(qs * sq[:, None]).astype(jnp.int8)
            dots = jnp.einsum(
                "nd,bd->bn", x8, q8, preferred_element_type=jnp.int32
            )
            approx = dots.astype(jnp.float32) * (arow[None, :]
                                                 / sq[:, None])
            if metric == "euclidean":
                score = x2[None, :] - 2.0 * approx
            else:  # cosine (pre-normalized rows) / dot
                score = -approx
            score = jnp.where(valid[None, :], score, jnp.inf)
            neg, loc = jax.lax.top_k(-score, kc_l)
            gid = jnp.minimum(loc + base[0], n - 1).astype(jnp.int32)
            s_all = jax.lax.all_gather(neg, MESH_AXIS, axis=1, tiled=True)
            i_all = jax.lax.all_gather(gid, MESH_AXIS, axis=1, tiled=True)
            _, sel = jax.lax.top_k(s_all, kc_out)
            return jnp.take_along_axis(i_all, sel, axis=1)

        return jax.jit(jax.shard_map(
            shard, mesh=mesh,
            in_specs=(P(MESH_AXIS, None), P(MESH_AXIS),
                      P(MESH_AXIS), P(MESH_AXIS), P(MESH_AXIS),
                      P(None, None)),
            out_specs=P(None, None),
            check_vma=False,
        ))

    key = ("vec_int8", mesh, dim, nloc, chunk, kc_l, kc_out, metric)
    return _jit_entry("mesh_vec_int8", key, build)


class MeshVecStore:
    """Row-sharded vector blocks for ONE cache epoch on a device mesh.

    Same (key, tag) ship protocol and knn() contract as VecStore — the
    serving process ships the full arrays once; the runner slices at
    install time. Kernel selection: non-MXU metrics and MXU stores
    whose per-device 6 B/elem share fits HBM run the exact kernel
    (mode "pairs"); larger MXU stores run int8 ranking (mode "cand",
    exact rescore on the serving side, unchanged)."""

    def __init__(self, key: str, vecs: np.ndarray, valid: np.ndarray,
                 metric: str, mink_p: float, cfg: dict, ndev: int,
                 offsets=None):
        self.key = key
        self.vecs = vecs
        self.shape = tuple(int(v) for v in vecs.shape)  # as VecStore's
        self.growable = False  # a mesh store takes whole loads only
        self.valid = valid.astype(bool)
        self.metric = metric
        self.mink_p = float(mink_p)
        self.cfg = dict(cfg)  # robust: mem-account (per-dispatch knobs, fixed keys)
        self.mesh_ndev = max(int(ndev), 1)
        n, dim = vecs.shape
        self.offsets = (  # robust: mem-account (ndev+1 fenceposts, fixed at install)
            [int(o) for o in offsets] if offsets is not None
            else even_splits(n, self.mesh_ndev)
        )
        _check_offsets(self.offsets, n, self.mesh_ndev)
        if not exact_store(metric, self.cfg) \
                and (6 * n * dim) // self.mesh_ndev \
                > self.cfg.get("hbm_budget", 1 << 62):
            self.rank_mode = "int8"
        else:
            self.rank_mode = None  # exact store
        self.mesh = None
        self._dev = None
        self._nloc = 0

    def nbytes(self) -> int:
        return int(self.vecs.nbytes)

    @staticmethod
    def estimate_device_bytes(n: int, dim: int, itemsize: int,
                              metric: str, cfg: dict, ndev: int) -> int:
        """TOTAL device bytes across the mesh once ensured (padding
        included) — `pick_ndev`/`_admit` divide by ndev for the
        per-device share. Mirrors `ensure()`'s branches."""
        ndev = max(int(ndev), 1)
        n = max(int(n), 0)
        dim = max(int(dim), 1)
        nloc = -(-n // ndev) if n else 1
        if not exact_store(metric, cfg) and (6 * n * dim) // ndev \
                > cfg.get("hbm_budget", 1 << 62):
            # int8 ranking: rows (1 B/elem) + arow/x2 f32 + valid + base
            return ndev * nloc * (dim + 9) + 4 * ndev
        # exact store: raw rows + the validity mask + base
        return ndev * nloc * (dim * itemsize + 1) + 4 * ndev

    def device_nbytes(self) -> int:
        n, dim = self.vecs.shape
        return self.estimate_device_bytes(
            n, dim, self.vecs.dtype.itemsize, self.metric, self.cfg,
            self.mesh_ndev,
        )

    def ensure(self):
        if self._dev is not None:
            return
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        ndev = self.mesh_ndev
        devs = jax.devices()[:ndev]
        if len(devs) < ndev:
            raise RuntimeError(
                f"mesh store {self.key!r} placed on {ndev} devices but "
                f"the runner has {len(devs)}"
            )
        self.mesh = _make_mesh(devs)
        offs = self.offsets
        n, dim = self.vecs.shape
        nloc = max(max(offs[s + 1] - offs[s] for s in range(ndev)), 1)
        self._nloc = nloc
        base = np.asarray(offs[:-1], np.int32)
        sh_rows = NamedSharding(self.mesh, P(MESH_AXIS, None))
        sh_vec = NamedSharding(self.mesh, P(MESH_AXIS))
        valid_p = _pack(self.valid, offs, nloc, False)
        if self.rank_mode == "int8":
            # identical per-row quantization to VecStore.ensure()'s
            # int8 branch (f64-accurate stats over the FULL store,
            # then slice): per-row math is shard-independent, so the
            # shipped bytes equal the single-device bytes
            xs = self.vecs
            norms = None
            x2 = np.zeros(n, np.float32)
            if self.metric == "euclidean":
                x2 = (xs.astype(np.float64) ** 2).sum(axis=1).astype(
                    np.float32)
            elif self.metric == "cosine":
                norms = np.maximum(
                    np.linalg.norm(xs.astype(np.float64), axis=1), 1e-30
                ).astype(np.float32)
            x8 = np.empty((n, dim), np.int8)
            arow = np.empty(n, np.float32)
            step = max(1, (256 << 20) // max(dim * 4, 1))
            for s in range(0, n, step):
                blk = xs[s:s + step].astype(np.float32)
                if norms is not None:
                    blk = blk / norms[s:s + step, None]
                m = np.maximum(np.abs(blk).max(axis=1), 1e-30)
                x8[s:s + step] = np.rint(
                    blk * (127.0 / m)[:, None]
                ).astype(np.int8)
                arow[s:s + step] = m / 127.0
            self._dev = (
                jax.device_put(_pack(x8, offs, nloc), sh_rows),
                jax.device_put(_pack(arow, offs, nloc), sh_vec),
                jax.device_put(_pack(x2, offs, nloc), sh_vec),
                jax.device_put(valid_p, sh_vec),
                jax.device_put(base, sh_vec),
            )
            return
        self._dev = (
            jax.device_put(_pack(self.vecs, offs, nloc), sh_rows),
            jax.device_put(valid_p, sh_vec),
            jax.device_put(np.asarray(base), sh_vec),
        )

    def knn(self, qvs: np.ndarray, k: int):
        """Batched mesh search: [B, D] f32 queries -> (meta, bufs) with
        the exact VecStore.knn() contract plus meta["mesh_ndev"]."""
        self.ensure()
        from surrealdb_tpu.device.kernelstats import (
            note_shape, note_sharded,
        )
        from surrealdb_tpu.device.vecstore import _pow2_chunks

        cfg = self.cfg
        n, dim = self.vecs.shape
        ndev = self.mesh_ndev
        nloc = self._nloc
        b_total = qvs.shape[0]
        k = max(int(k), 1)

        def chunks(budget):
            _b, chunk, _r = _pow2_chunks(
                b_total, nloc, cfg["query_chunk"], budget
            )
            return chunk

        def run(fn, chunk):
            parts = []
            for s in range(0, b_total, chunk):
                qc = np.ascontiguousarray(qvs[s:s + chunk], np.float32)
                if qc.shape[0] < chunk:
                    qc = np.pad(qc, ((0, chunk - qc.shape[0]), (0, 0)))
                parts.append(fn(*self._dev, qc))
            return parts

        if self.rank_mode == "int8":
            kc = min(n, max(cfg["int8_oversample"] * k, k + 16))
            kc_l = min(kc, nloc)
            kc_out = min(kc, ndev * kc_l)
            chunk = chunks(cfg["score_budget"] // 2)
            fn = _vec_int8_jit(self.mesh, dim, nloc, chunk, kc_l, kc_out,
                               self.metric, n)
            note_shape("mesh_vec_int8",
                       (self.vecs.shape, ndev, chunk, kc_out, self.metric))
            note_sharded("mesh_vec_int8", ndev)
            cand = np.concatenate(
                [np.asarray(c) for c in run(fn, chunk)]
            )[:b_total]
            return (
                {"mode": "cand", "rank_mode": "int8", "kc": kc_out,
                 "mesh_ndev": ndev},
                [np.ascontiguousarray(cand, np.int32)],
            )
        k_l = min(k, nloc)
        k_out = min(k, ndev * k_l)
        chunk = chunks(cfg["score_budget"])
        fn = _vec_exact_jit(self.mesh, dim, nloc, chunk, k_l, k_out,
                            self.metric, self.mink_p, n)
        note_shape("mesh_vec_exact",
                   (self.vecs.shape, ndev, chunk, k_out, self.metric))
        note_sharded("mesh_vec_exact", ndev)
        d_parts = []
        i_parts = []
        for dc, ic in run(fn, chunk):
            d_parts.append(np.asarray(dc))
            i_parts.append(np.asarray(ic))
        return (
            {"mode": "pairs", "rank_mode": None, "mesh_ndev": ndev},
            [
                np.ascontiguousarray(np.concatenate(d_parts)[:b_total],
                                     np.float32),
                np.ascontiguousarray(np.concatenate(i_parts)[:b_total],
                                     np.int32),
            ],
        )


# -- sharded graph-ANN store ---------------------------------------------


def _ann_jit(mesh, shapes, statics):
    def build():
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from surrealdb_tpu.device.annstore import _descent_scored

        metric, width, iters, expand, kc_l, kc_out, n = statics

        def shard(graph, x8, arow, x2q, x8p, arowp, x2qp, probe_ids,
                  base, qs):
            ids_l, dist_l = _descent_scored(
                graph, x8, arow, x2q, x8p, arowp, x2qp, probe_ids, qs,
                metric, width, iters, expand, kc_l,
            )
            gid = jnp.minimum(ids_l + base[0], n - 1).astype(jnp.int32)
            d_all = jax.lax.all_gather(dist_l, MESH_AXIS, axis=1,
                                       tiled=True)
            i_all = jax.lax.all_gather(gid, MESH_AXIS, axis=1, tiled=True)
            _, sel = jax.lax.top_k(-d_all, kc_out)
            return jnp.take_along_axis(i_all, sel, axis=1)

        row = P(MESH_AXIS, None)
        vec = P(MESH_AXIS)
        return jax.jit(jax.shard_map(
            shard, mesh=mesh,
            in_specs=(row, row, vec, vec, row, vec, vec, vec, vec,
                      P(None, None)),
            out_specs=P(None, None),
            check_vma=False,
        ))

    key = ("ann_descent", mesh) + shapes + statics
    return _jit_entry("mesh_ann_descent", key, build)


class MeshAnnStore:
    """Row-sharded CAGRA-style graph index for ONE build snapshot.

    Partitioned descent: each device owns a contiguous row slice with
    the graph's foreign edges remapped to self-loops (the descent's dup
    mask scores them +inf, so they cost an expansion slot, not a wrong
    answer) and its own strided routing probe; per-device candidates
    merge by (int8 score, global id) on-mesh. Every slice must be
    non-empty (`pick_ndev` clamps to n_rows)."""

    def __init__(self, key: str, graph: np.ndarray, x8: np.ndarray,
                 arow: np.ndarray, x2q: np.ndarray, metric: str,
                 cfg: dict, ndev: int, offsets=None):
        self.key = key
        self.graph = graph
        self.x8 = x8
        self.arow = arow
        self.x2q = x2q
        self.metric = metric
        self.cfg = dict(cfg)  # robust: mem-account (per-dispatch knobs, fixed keys)
        self.mesh_ndev = max(int(ndev), 1)
        n = x8.shape[0]
        self.offsets = (  # robust: mem-account (ndev+1 fenceposts, fixed at install)
            [int(o) for o in offsets] if offsets is not None
            else even_splits(n, self.mesh_ndev)
        )
        _check_offsets(self.offsets, n, self.mesh_ndev, allow_empty=False)
        self.mesh = None
        self._dev = None
        self._nloc = 0
        self._minlen = 0
        self._plen = 0

    def nbytes(self) -> int:
        return int(self.graph.nbytes + self.x8.nbytes
                   + self.arow.nbytes + self.x2q.nbytes)

    @staticmethod
    def estimate_device_bytes(n: int, dim: int, d_out: int,
                              ndev: int) -> int:
        """TOTAL device bytes across the mesh (AnnStore's formula per
        padded slice + per-slice probe rows)."""
        ndev = max(int(ndev), 1)
        n = max(int(n), 0)
        nloc = -(-n // ndev) if n else 1
        probe = min(nloc, max(4096, nloc // 8))
        return ndev * nloc * (4 * max(int(d_out), 1)
                              + max(int(dim), 1) + 8) \
            + ndev * probe * (max(int(dim), 1) + 12)

    def device_nbytes(self) -> int:
        n, dim = self.x8.shape
        return self.estimate_device_bytes(
            n, dim, self.graph.shape[1], self.mesh_ndev
        )

    def _ensure(self):
        if self._dev is not None:
            return
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from surrealdb_tpu.idx.cagra import entry_ids, probe_count

        ndev = self.mesh_ndev
        devs = jax.devices()[:ndev]
        if len(devs) < ndev:
            raise RuntimeError(
                f"mesh ANN store {self.key!r} placed on {ndev} devices "
                f"but the runner has {len(devs)}"
            )
        self.mesh = _make_mesh(devs)
        offs = self.offsets
        n, dim = self.x8.shape
        d_out = self.graph.shape[1]
        lens = [offs[s + 1] - offs[s] for s in range(ndev)]
        nloc = max(lens)
        minlen = min(lens)
        self._nloc, self._minlen = nloc, minlen
        w = max(int(self.cfg.get("width", 64)), 1)
        # one probe size for every slice (uniform shard shapes): the
        # nloc-sized probe budget clamped to the smallest slice
        plen = max(1, min(minlen, probe_count(nloc, w)))
        self._plen = plen
        graph_l = np.zeros((ndev * nloc, d_out), np.int32)
        x8p = np.zeros((ndev * plen, dim), np.int8)
        arowp = np.zeros(ndev * plen, np.float32)
        x2qp = np.zeros(ndev * plen, np.float32)
        pids = np.zeros(ndev * plen, np.int32)
        for s in range(ndev):
            lo, hi = offs[s], offs[s + 1]
            g = self.graph[lo:hi].astype(np.int64)
            local = g - lo
            own = np.arange(hi - lo, dtype=np.int64)[:, None]
            inside = (g >= lo) & (g < hi)
            graph_l[s * nloc:s * nloc + (hi - lo)] = np.where(
                inside, local, own
            ).astype(np.int32)
            pl = entry_ids(hi - lo, plen).astype(np.int64)
            x8p[s * plen:(s + 1) * plen] = self.x8[lo + pl]
            arowp[s * plen:(s + 1) * plen] = self.arow[lo + pl]
            x2qp[s * plen:(s + 1) * plen] = self.x2q[lo + pl]
            pids[s * plen:(s + 1) * plen] = pl.astype(np.int32)
        base = np.asarray(offs[:-1], np.int32)
        sh_rows = NamedSharding(self.mesh, P(MESH_AXIS, None))
        sh_vec = NamedSharding(self.mesh, P(MESH_AXIS))
        self._host = (
            graph_l, _pack(self.x8, offs, nloc),
            _pack(self.arow, offs, nloc), _pack(self.x2q, offs, nloc),
            x8p, arowp, x2qp, pids, base,
        )
        self._dev = tuple(
            jax.device_put(a, sh_rows if a.ndim == 2 else sh_vec)
            for a in self._host
        )

    def _clamps(self, kc: int):
        cfg = self.cfg
        n = self.x8.shape[0]
        width = max(int(cfg.get("width", 64)), 1)
        iters = max(int(cfg.get("iters", 24)), 1)
        expand = max(int(cfg.get("expand", 2)), 1)
        kc = min(max(int(kc), 1), n)
        # per-shard clamps: AnnStore.search()'s rules against the
        # SMALLEST slice so every device runs the same static shapes
        kc_l = min(kc, self._minlen)
        width_l = min(max(width, kc_l), self._minlen, self._plen)
        kc_l = min(kc_l, width_l)
        expand_l = min(expand, width_l)
        kc_out = min(kc, self.mesh_ndev * kc_l)
        return width_l, iters, expand_l, kc_l, kc_out

    @staticmethod
    def _bucket(qs: np.ndarray):
        b = qs.shape[0]
        bucket = 1
        while bucket < b:
            bucket *= 2
        qsb = np.ascontiguousarray(qs, np.float32)
        if bucket != b:
            qsb = np.concatenate(
                [qsb, np.zeros((bucket - b, qsb.shape[1]), np.float32)]
            )
        return qsb, b

    def search(self, qs: np.ndarray, kc: int) -> np.ndarray:
        """[B, D] f32 queries -> [B, kc'] int32 candidate ids, merged
        on-mesh from the per-device partial descents."""
        import jax.numpy as jnp

        from surrealdb_tpu.device.kernelstats import (
            note_shape, note_sharded,
        )

        self._ensure()
        width_l, iters, expand_l, kc_l, kc_out = self._clamps(kc)
        qsb, b = self._bucket(qs)
        statics = (self.metric, width_l, iters, expand_l, kc_l, kc_out,
                   self.x8.shape[0])
        shapes = (self._nloc, self.x8.shape[1], self.graph.shape[1],
                  self._plen, qsb.shape[0])
        note_shape("mesh_ann_descent", shapes + statics
                   + (self.mesh_ndev,))
        note_sharded("mesh_ann_descent", self.mesh_ndev)
        fn = _ann_jit(self.mesh, shapes, statics)
        cand = fn(*self._dev, jnp.asarray(qsb))
        return np.ascontiguousarray(np.asarray(cand)[:b], np.int32)

    def search_seq(self, qs: np.ndarray, kc: int) -> np.ndarray:
        """Byte-identity oracle: the SAME partitioned descent run slice
        by slice on one device (annstore._descent_jit) and merged by
        (dist, gather-position) with `lax.top_k`'s tie rule — what the
        mesh kernel must reproduce exactly."""
        import jax.numpy as jnp

        from surrealdb_tpu.device.annstore import _descent_jit

        self._ensure()
        ndev = self.mesh_ndev
        width_l, iters, expand_l, kc_l, kc_out = self._clamps(kc)
        qsb, b = self._bucket(qs)
        (graph_l, x8_p, arow_p, x2q_p, x8p, arowp, x2qp, pids,
         base) = self._host
        nloc, plen = self._nloc, self._plen
        d_parts = []
        i_parts = []
        for s in range(ndev):
            args = (
                jnp.asarray(graph_l[s * nloc:(s + 1) * nloc]),
                jnp.asarray(x8_p[s * nloc:(s + 1) * nloc]),
                jnp.asarray(arow_p[s * nloc:(s + 1) * nloc]),
                jnp.asarray(x2q_p[s * nloc:(s + 1) * nloc]),
                jnp.asarray(x8p[s * plen:(s + 1) * plen]),
                jnp.asarray(arowp[s * plen:(s + 1) * plen]),
                jnp.asarray(x2qp[s * plen:(s + 1) * plen]),
                jnp.asarray(pids[s * plen:(s + 1) * plen]),
                jnp.asarray(qsb),
            )
            ids_l, dist_l = _descent_jit(
                args, (self.metric, width_l, iters, expand_l, kc_l),
                scored=True,
            )
            i_parts.append(np.minimum(
                np.asarray(ids_l).astype(np.int64) + base[s],
                self.x8.shape[0] - 1,
            ).astype(np.int32))
            d_parts.append(np.asarray(dist_l))
        dist = np.concatenate(d_parts, axis=1)
        gids = np.concatenate(i_parts, axis=1)
        order = np.argsort(dist, axis=1, kind="stable")[:, :kc_out]
        return np.ascontiguousarray(
            np.take_along_axis(gids, order, axis=1)[:b], np.int32
        )


# -- sharded CSR graph store ---------------------------------------------


def _csr_jit(mesh, eloc, n_nodes, hops, union, bucket):
    def build():
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        def shard(rows, cols, w, start):
            def hop(frontier, _):
                # per-device partial scatter-add over the local edge
                # slice (w=0 kills padding edges), summed exactly
                # across the mesh — integer adds are associative, so
                # the frontier equals the single-device scan bitwise
                contrib = frontier[:, rows].astype(jnp.int32) * w[None, :]
                part = jnp.zeros(frontier.shape, jnp.int32).at[
                    :, cols
                ].add(contrib)
                nxt = jax.lax.psum(part, MESH_AXIS) > 0
                return nxt, nxt

            frontier, layers = jax.lax.scan(hop, start, None, length=hops)
            if union:
                return layers.any(axis=0)
            return frontier

        vec = P(MESH_AXIS)
        return jax.jit(jax.shard_map(
            shard, mesh=mesh,
            in_specs=(vec, vec, vec, P(None, None)),
            out_specs=P(None, None),
            check_vma=False,
        ))

    key = ("csr_hop", mesh, eloc, n_nodes, hops, union, bucket)
    return _jit_entry("mesh_csr_hop", key, build)


class MeshCsrStore:
    """Edge-sharded adjacency for ONE graph cache epoch: each device
    scatter-adds its contiguous edge slice, `psum` merges the partial
    frontiers — byte-identical to CsrStore's single-device scan."""

    def __init__(self, key: str, rows: np.ndarray, cols: np.ndarray,
                 n_nodes: int, ndev: int, offsets=None):
        self.key = key
        self.n_nodes = int(n_nodes)
        self.rows = rows
        self.cols = cols
        self.mesh_ndev = max(int(ndev), 1)
        e = rows.shape[0]
        self.offsets = (  # robust: mem-account (ndev+1 fenceposts, fixed at install)
            [int(o) for o in offsets] if offsets is not None
            else even_splits(e, self.mesh_ndev)
        )
        _check_offsets(self.offsets, e, self.mesh_ndev)
        self.mesh = None
        self._dev = None
        self._eloc = 0

    def nbytes(self) -> int:
        return int(self.rows.nbytes + self.cols.nbytes)

    @staticmethod
    def estimate_device_bytes(e: int, ndev: int) -> int:
        """TOTAL device bytes: two int32 edge arrays + the int32
        padding mask, padded per slice."""
        ndev = max(int(ndev), 1)
        eloc = -(-max(int(e), 0) // ndev) if e else 1
        return ndev * eloc * 12

    def device_nbytes(self) -> int:
        return self.estimate_device_bytes(self.rows.shape[0],
                                          self.mesh_ndev)

    def _ensure(self):
        if self._dev is not None:
            return
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        ndev = self.mesh_ndev
        devs = jax.devices()[:ndev]
        if len(devs) < ndev:
            raise RuntimeError(
                f"mesh CSR store {self.key!r} placed on {ndev} devices "
                f"but the runner has {len(devs)}"
            )
        self.mesh = _make_mesh(devs)
        offs = self.offsets
        eloc = max(max(offs[s + 1] - offs[s] for s in range(ndev)), 1)
        self._eloc = eloc
        w = np.ones(self.rows.shape[0], np.int32)
        sh = NamedSharding(self.mesh, P(MESH_AXIS))
        self._dev = (
            jax.device_put(
                _pack(self.rows.astype(np.int32), offs, eloc), sh),
            jax.device_put(
                _pack(self.cols.astype(np.int32), offs, eloc), sh),
            jax.device_put(_pack(w, offs, eloc), sh),
        )

    def multi_hop(self, start: np.ndarray, hops: int,
                  union: bool) -> np.ndarray:
        """CsrStore.multi_hop's exact contract over the mesh."""
        import jax.numpy as jnp

        from surrealdb_tpu.device.kernelstats import (
            note_shape, note_sharded,
        )

        self._ensure()
        single = start.ndim == 1
        masks = start[None, :] if single else start
        b = masks.shape[0]
        bucket = 1
        while bucket < b:
            bucket *= 2
        if bucket != b:
            masks = np.concatenate(
                [masks, np.zeros((bucket - b, masks.shape[1]),
                                 masks.dtype)]
            )
        fn = _csr_jit(self.mesh, self._eloc, self.n_nodes, int(hops),
                      bool(union), bucket)
        note_shape("mesh_csr_hop", (self.n_nodes, self._eloc,
                                    self.mesh_ndev, int(hops),
                                    bool(union), bucket))
        note_sharded("mesh_csr_hop", self.mesh_ndev)
        out = fn(*self._dev, jnp.asarray(masks.astype(bool)))
        out = np.asarray(out)[:b].astype(np.uint8)
        return out[0] if single else out


# -- selfcheck / proof entry points --------------------------------------


def selfcheck(max_devices=None, seed: int = 0) -> dict:
    """Property sweep across pow2 device counts AND random contiguous
    row splits: sharded brute (the matmul metric: identical ids,
    distances within an f32 tolerance; the non-matmul metric: equal
    bytes), and byte-identity for int8 ranking, partitioned ANN descent
    (vs `search_seq`) and CSR multi-hop (vs the single-device
    CsrStore) — integer arithmetic. Returns a report dict;
    ok=False on the first divergence. Runs on whatever devices jax
    sees — drive with XLA_FLAGS=--xla_force_host_platform_device_count
    (or `python -m surrealdb_tpu.device.mesh`)."""
    import jax

    from surrealdb_tpu.device.csrstore import CsrStore

    navail = int(jax.device_count())
    cap = min(navail, int(max_devices)) if max_devices else navail
    counts = [d for d in (1, 2, 4, 8) if d <= cap]
    rng = np.random.default_rng(seed)
    checks: dict = {}
    report = {"n_devices": navail, "counts": counts, "checks": checks}

    def rand_offsets(n, ndev):
        cut = np.sort(rng.choice(np.arange(1, n), size=ndev - 1,
                                 replace=False))
        return [0] + [int(c) for c in cut] + [n]

    n, dim, k, nq = 257, 16, 10, 5
    xs = rng.normal(size=(n, dim)).astype(np.float32)
    valid = np.ones(n, bool)
    valid[rng.choice(n, 20, replace=False)] = False
    qs = (xs[rng.integers(0, n, nq)]
          + 0.1 * rng.normal(size=(nq, dim))).astype(np.float32)
    cfg = {"hbm_budget": 1 << 62, "score_budget": 1 << 22,
           "query_chunk": 64, "int8_oversample": 4,
           "block_rows": 1 << 20}

    def sweep(n_items, make, run, ref=None, same=lambda a, b: a == b):
        """run(store) -> result; `same` across every (ndev, split) and
        against `ref` when a single-device oracle is supplied (default:
        equal bytes)."""
        for d in counts:
            splits = [even_splits(n_items, d)]
            if d > 1 and n_items >= d:
                splits.append(rand_offsets(n_items, d))
            for offs in splits:
                cur = run(make(d, offs))
                if ref is None:
                    ref = cur
                elif not same(cur, ref):
                    return False
        return True

    def same_matmul(a, b):
        # a matmul's f32 rounding depends on how XLA tiles the row
        # slice, so |x|²+|q|²-2x·q is not bitwise split-invariant (it
        # is not on the CPU backend either). The guarantee is identical
        # ids, and distances inside the cancellation bound of the f32
        # formula: eps·(|x|²+|q|²+2|x·q|) ≈ 1e-5 on d² at these norms,
        # ≈ 2e-5 on d at the smallest distances here — 1e-4 with room.
        (da, ia), (db, ib) = a, b
        return bool(np.array_equal(ia, ib)
                    and np.allclose(da, db, rtol=1e-5, atol=1e-4))

    checks["vec_exact_euclidean"] = sweep(
        n,
        lambda d, offs: MeshVecStore(
            "chk/euclidean", xs, valid, "euclidean", 3.0, cfg, d, offs),
        lambda st: tuple(st.knn(qs, k)[1]),
        same=same_matmul,
    )
    checks["vec_exact_manhattan"] = sweep(
        n,
        lambda d, offs: MeshVecStore(
            "chk/manhattan", xs, valid, "manhattan", 3.0, cfg, d, offs),
        lambda st: b"".join(bb.tobytes() for bb in st.knn(qs, k)[1]),
    )
    cfg8 = dict(cfg, hbm_budget=0)  # force the int8 ranking branch
    checks["vec_int8"] = sweep(
        n,
        lambda d, offs: MeshVecStore(
            "chk/int8", xs, valid, "euclidean", 3.0, cfg8, d, offs),
        lambda st: st.knn(qs, k)[1][0].tobytes(),
    )
    # partitioned descent: mesh collectives vs the sequential oracle of
    # the SAME partition (per-(ndev, split) identity — the partition
    # itself legitimately changes the candidate walk)
    x8 = np.clip(np.rint(xs * 32), -127, 127).astype(np.int8)
    arow = np.full(n, 1 / 32.0, np.float32)
    x2q = (xs.astype(np.float64) ** 2).sum(axis=1).astype(np.float32)
    graph = rng.integers(0, n, size=(n, 8)).astype(np.int32)
    acfg = {"width": 32, "iters": 6, "expand": 2}
    ok = True
    for d in counts:
        splits = [even_splits(n, d)]
        if d > 1:
            splits.append(rand_offsets(n, d))
        for offs in splits:
            st = MeshAnnStore("chk/ann", graph, x8, arow, x2q,
                              "euclidean", acfg, d, offs)
            if st.search(qs, 16).tobytes() != \
                    st.search_seq(qs, 16).tobytes():
                ok = False
    checks["ann_descent_vs_seq"] = ok
    n_nodes, n_edges = 64, 400
    rows = rng.integers(0, n_nodes, n_edges).astype(np.int32)
    cols = rng.integers(0, n_nodes, n_edges).astype(np.int32)
    starts = np.zeros((3, n_nodes), np.uint8)
    starts[np.arange(3), rng.integers(0, n_nodes, 3)] = 1
    single = CsrStore("chk/csr0", rows, cols, n_nodes)
    for hops, union in ((1, False), (3, True)):
        ref = single.multi_hop(starts, hops, union).tobytes()
        checks[f"csr_hop{hops}{'u' if union else ''}"] = sweep(
            n_edges,
            lambda d, offs: MeshCsrStore(
                "chk/csr", rows, cols, n_nodes, d, offs),
            lambda st, h=hops, u=union:
                st.multi_hop(starts, h, u).tobytes(),
            ref=ref,
        )
    report["ok"] = all(checks.values())
    report["sharded_kernel_ran"] = max(counts) > 1
    return report


def _budget_store():
    """The over-budget store both budget proofs ship: a manhattan
    (non-MXU → exact) store of ~2.1 MB against a 1 MiB per-device
    budget — fits at ndev=4, not at 1."""
    n, dim = 8192, 64
    rng = np.random.default_rng(7)
    xs = rng.normal(size=(n, dim)).astype(np.float32)
    valid = np.ones(n, bool)
    meta = {
        "key": "budget/chk", "tag": ["t1"], "metric": "manhattan",
        "mink_p": 3.0,
        "cfg": {"hbm_budget": 1 << 62, "score_budget": 1 << 22,
                "query_chunk": 64, "int8_oversample": 4,
                "block_rows": 1 << 20},
    }
    return xs, valid, meta


def refusal_probe(budget_bytes: int = 1 << 20) -> dict:
    """Negative half of the placement proof, run in a 1-device process
    (`--devices 1 --refusal-probe`): the same store must be REFUSED
    when there is no mesh to widen onto."""
    import jax

    from surrealdb_tpu.device.handlers import DeviceBudgetError, DeviceHost

    xs, valid, meta = _budget_store()
    host = DeviceHost()
    host.budget_bytes = int(budget_bytes)
    out = {"n_devices": int(jax.device_count()),
           "budget_bytes": int(budget_bytes)}
    try:
        host.handle("vec_load", dict(meta), [xs, valid])
        out["refused"] = False
    except DeviceBudgetError as e:
        out["refused"] = True
        out["refusal"] = str(e)
    out["ok"] = bool(out["refused"] and out["n_devices"] == 1)
    return out


def budget_check(budget_bytes: int = 1 << 20) -> dict:
    """Per-device budget placement proof: a store whose single-device
    estimate is over budget SERVES SHARDED on this (multi-device)
    host, and the SAME ship is refused by a 1-virtual-device
    subprocess (`refusal_probe`) — fits on the mesh, not on one chip."""
    import json
    import subprocess
    import sys

    from surrealdb_tpu.device.handlers import DeviceHost

    xs, valid, meta = _budget_store()
    qs = xs[:3] + 0.1
    out: dict = {"budget_bytes": int(budget_bytes)}
    saved = os.environ.get("SURREAL_DEVICE_MESH")
    try:
        os.environ["SURREAL_DEVICE_MESH"] = "auto"
        host = DeviceHost()
        host.budget_bytes = int(budget_bytes)
        tag, lmeta, _ = host.handle("vec_load", dict(meta), [xs, valid])
        out["load"] = tag
        out["mesh_ndev"] = int(lmeta.get("mesh_ndev", 1))
        tag, kmeta, bufs = host.handle(
            "vec_knn", {"key": meta["key"], "tag": meta["tag"], "k": 5},
            [qs],
        )
        out["knn"] = tag
        out["knn_mesh_ndev"] = int(kmeta.get("mesh_ndev", 1))
        out["sharded_served"] = (
            tag == "ok" and out["mesh_ndev"] >= 2
            and out["knn_mesh_ndev"] >= 2
            and bufs[1].shape == (3, 5)
        )
    finally:
        if saved is None:
            os.environ.pop("SURREAL_DEVICE_MESH", None)
        else:
            os.environ["SURREAL_DEVICE_MESH"] = saved
    r = subprocess.run(
        [sys.executable, "-m", "surrealdb_tpu.device.mesh",
         "--devices", "1", "--refusal-probe"],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    try:
        probe = json.loads(r.stdout.strip().splitlines()[-1])
    except Exception:
        probe = {"ok": False, "stderr": r.stderr[-500:]}
    out["refusal_probe"] = probe
    out["single_device_refused"] = bool(probe.get("refused"))
    out["ok"] = bool(out.get("sharded_served") and probe.get("ok"))
    return out


def _force_virtual_devices(n: int):
    """Pin the virtual CPU device count for this process — REPLACES
    any inherited --xla_force_host_platform_device_count so a child
    spawned with --devices 1 isn't poisoned by the parent's =8. Only
    effective before the first jax import."""
    import re
    import sys

    if "jax" in sys.modules:
        return
    flags = os.environ.get("XLA_FLAGS", "")
    flags = re.sub(
        r"--xla_force_host_platform_device_count=\d+", "", flags
    ).strip()
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={int(n)}"
    ).strip()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _main(argv=None) -> int:
    import argparse
    import json

    ap = argparse.ArgumentParser(prog="surrealdb_tpu.device.mesh")
    ap.add_argument("--devices", type=int, default=8,
                    help="virtual CPU device count to force")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--budget-check", action="store_true",
                    help="also prove per-device budget placement")
    ap.add_argument("--refusal-probe", action="store_true",
                    help="run only the 1-device budget refusal probe")
    args = ap.parse_args(argv)
    _force_virtual_devices(args.devices)
    if args.refusal_probe:
        rep = refusal_probe()
        print(json.dumps(rep))
        return 0 if rep["ok"] else 1
    rep = selfcheck(max_devices=args.devices, seed=args.seed)
    if args.budget_check:
        rep["budget"] = budget_check()
        rep["ok"] = bool(rep["ok"] and rep["budget"]["ok"])
    print(json.dumps(rep))
    return 0 if rep["ok"] else 1


if __name__ == "__main__":
    raise SystemExit(_main())
