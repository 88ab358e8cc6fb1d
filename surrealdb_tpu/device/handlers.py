"""Device op dispatch table, shared by the DeviceRunner subprocess and
the `SURREAL_DEVICE=inline` debug mode.

Every handler is `(meta, bufs) -> (tag, meta_out, bufs_out)`; raising
maps to an `("err", ...)` reply. The store caches are bounded LRU — an
evicted store simply answers "stale" on its next use and the serving
side re-ships (device blocks are a cache over KV truth)."""

from __future__ import annotations

import time
from collections import OrderedDict

import numpy as np

from surrealdb_tpu import cnf

# bounded block caches: enough for every live index in a busy node, and
# an eviction is only a re-ship (never an error)
MAX_VEC_STORES = 64
MAX_CSR_STORES = 64
MAX_ANN_STORES = 16
MAX_FT_STORES = 16


class DeviceBudgetError(RuntimeError):
    """A ship would exceed the runner's device-memory byte budget even
    after evicting every other store: this ONE store cannot be served.
    The runner stays healthy; the reply carries `oom: true` and the
    supervisor raises a typed `DeviceOutOfMemory`, degrading that store
    to the host paths (never wedging or killing the runner)."""


def _vec_estimate(n: int, dim: int, itemsize: int, meta: dict,
                  ndev: int = 0) -> int:
    """Install estimate: the mesh store's TOTAL bytes when the load is
    placed on a mesh (`ndev` >= 1), else the legacy VecStore formula."""
    if ndev:
        from surrealdb_tpu.device.mesh import MeshVecStore

        return MeshVecStore.estimate_device_bytes(
            n, dim, itemsize, meta["metric"], meta["cfg"], ndev
        )
    from surrealdb_tpu.device.vecstore import VecStore

    return VecStore.estimate_device_bytes(
        n, dim, itemsize, meta["metric"], meta["cfg"]
    )


def _store_ndev(store) -> int:
    """Devices a store's arrays actually live on, for REPORTING: the
    mesh stores' width, and equally the legacy VecStore's when it
    self-sharded over `jax.devices()` (device/vecstore.py `ensure`).
    Budget admission keeps reading `mesh_ndev`, which the legacy store
    lacks on purpose — its estimate is already a per-chip share."""
    mesh = getattr(store, "mesh", None)
    return int(mesh.devices.size) if mesh is not None else 1


class DeviceHost:
    """Per-runner registry of vector + CSR block caches."""

    def __init__(self):
        self.vec: OrderedDict = OrderedDict()  # key -> (tag, VecStore)
        self.csr: OrderedDict = OrderedDict()  # key -> (tag, CsrStore)
        self.ann: OrderedDict = OrderedDict()  # key -> (tag, AnnStore)
        self.ft: OrderedDict = OrderedDict()  # key -> (tag, FtStore)
        # multipart vec loads in flight: key -> (meta, vecs, valid).
        # Big stores (the 10M×768 regime is ~30 GB of f32 rows) ship as
        # begin/part.../end so no single frame has to hold the store.
        self._staging: dict = {}
        # multipart ANN loads: key -> (meta, {name: array}); the int8
        # rows and the graph ship as independently chunked buffers
        self._ann_staging: dict = {}
        # device-memory byte budget (SURREAL_DEVICE_MEM_BUDGET_MB;
        # 0 = entry-count caps only), interpreted PER DEVICE: every
        # resident store accounts its estimated device-0 share
        # (estimate / mesh_ndev — unsharded stores sit whole on device
        # 0, the max-loaded device of a mesh). A ship admits by
        # evicting LRU stores first (eviction = re-ship on next use,
        # never an error) and is REFUSED with DeviceBudgetError only
        # when the single store's per-device share cannot fit an
        # otherwise-empty runner — placement (device/mesh.pick_ndev)
        # first widens the mesh so a store that fits on 8 devices but
        # not 1 SHARDS instead of refusing.
        self.budget_bytes = cnf.env_int(
            "SURREAL_DEVICE_MEM_BUDGET_MB", cnf.DEVICE_MEM_BUDGET_MB
        ) << 20
        self.oom_refusals = 0
        self.budget_evictions = 0
        # dispatches answered "ok" per op since this runner started —
        # the runner's own evidence of what ran on the device
        self.op_counts: dict = {}  # robust: mem-account (one int per op name, bounded by the op table)
        # multipart install reservations: key -> final install SHARE
        # (device-0 bytes) admitted at *_load_begin but not yet
        # resident. Counted by mem_used()/mem_used_device0() so a
        # CONCURRENT ship admitted between one store's begin and end
        # cannot overcommit the budget; released when the staged store
        # installs (or its staging is dropped).
        self._reserved: dict = {}
        # graph builds' kNN passes in flight (`op_ann_knn_graph`): build
        # key -> (the span's rows on the device, its compiled program),
        # from a build's first call to its last; the rows stay reserved
        self._graphs: dict = {}  # robust: mem-account (one entry a build in flight, dropped by its last call; its rows are counted in _reserved)

    # -- device-memory budget ------------------------------------------------

    def mem_used(self) -> int:
        """Estimated device-resident bytes across the block caches
        plus multipart staging buffers (host-side in the runner, but
        they become device arrays at load_end — admitted up front)."""
        total = 0
        for cache in (self.vec, self.csr, self.ann, self.ft):
            for _tag, st in cache.values():
                total += st.device_nbytes()
        for _m, vecs, valid in self._staging.values():
            total += int(vecs.nbytes) + int(valid.nbytes)
        for _m, by_name in self._ann_staging.values():
            total += sum(int(a.nbytes) for a in by_name.values())
        total += sum(self._reserved.values())
        return total

    def mem_used_device0(self) -> int:
        """Estimated bytes on the MAX-LOADED device: sharded stores
        contribute their per-device share, unsharded stores (and
        staging buffers + reservations) their whole estimate — the
        quantity the per-device budget admits against."""
        total = 0
        for cache in (self.vec, self.csr, self.ann, self.ft):
            for _tag, st in cache.values():
                ndev = max(int(getattr(st, "mesh_ndev", 1) or 1), 1)
                total += -(-st.device_nbytes() // ndev)
        for _m, vecs, valid in self._staging.values():
            total += int(vecs.nbytes) + int(valid.nbytes)
        for _m, by_name in self._ann_staging.values():
            total += sum(int(a.nbytes) for a in by_name.values())
        total += sum(self._reserved.values())
        return total

    def _place_vec(self, n: int, dim: int, itemsize: int,
                   meta: dict) -> int:
        """Mesh width for a vec install: 0 = legacy single/self-sharded
        store (mesh off, one device, or a store that fits one device's
        budget), else the budget-aware pow2 count from
        device/mesh.pick_ndev."""
        from surrealdb_tpu.device import mesh as devmesh

        if devmesh.mesh_size() <= 1:
            return 0
        from surrealdb_tpu.device.mesh import MeshVecStore

        nd = devmesh.pick_ndev(
            lambda d: MeshVecStore.estimate_device_bytes(
                n, dim, itemsize, meta["metric"], meta["cfg"], d),
            self.budget_bytes, n_rows=max(n, 1),
        )
        return nd if nd > 1 else 0

    def _place_ann(self, n: int, dim: int, d_out: int) -> int:
        from surrealdb_tpu.device import mesh as devmesh

        if devmesh.mesh_size() <= 1:
            return 0
        from surrealdb_tpu.device.mesh import MeshAnnStore

        nd = devmesh.pick_ndev(
            lambda d: MeshAnnStore.estimate_device_bytes(n, dim, d_out,
                                                         d),
            self.budget_bytes, n_rows=max(n, 1),
        )
        return nd if nd > 1 else 0

    def _place_csr(self, n_edges: int) -> int:
        from surrealdb_tpu.device import mesh as devmesh

        if devmesh.mesh_size() <= 1:
            return 0
        from surrealdb_tpu.device.mesh import MeshCsrStore

        nd = devmesh.pick_ndev(
            lambda d: MeshCsrStore.estimate_device_bytes(n_edges, d),
            self.budget_bytes, n_rows=max(n_edges, 1),
        )
        return nd if nd > 1 else 0

    def _evict_key(self, key: str):
        """Drop any resident copy of `key` ahead of its replacement
        ship: a re-shipped store must never be refused because its own
        OUTDATED copy is counted against (and protected from) the
        budget."""
        for cache in (self.vec, self.csr, self.ann, self.ft):
            cache.pop(key, None)

    def _admit(self, incoming: int, keep_key: str = "", ndev: int = 1):
        """Admit `incoming` total estimated bytes sharded over `ndev`
        devices: the per-device budget sees `ceil(incoming/ndev)` —
        at ndev=1 (unsharded) exactly the old whole-estimate rule."""
        self._admit_share(
            -(-int(incoming) // max(int(ndev), 1)), keep_key
        )

    def _admit_share(self, share: int, keep_key: str = ""):
        """Make room for `share` estimated device-0 bytes or raise
        DeviceBudgetError. Victims pop oldest-first within each cache
        (the per-kind OrderedDicts are LRU — every use move_to_end's),
        in fixed kind order csr → ft → vec → ann: ascending re-ship cost,
        since an evicted store only ever answers `stale` and gets
        re-shipped from KV truth. `keep_key` (the incoming store,
        whose old copy `_evict_key` already dropped) is never a
        victim."""
        if self.budget_bytes <= 0:
            return
        if keep_key:
            # the old copy is outdated (tag mismatch would answer
            # `stale` regardless): free it instead of letting it count
            # against — and be protected from — its own replacement
            self._evict_key(keep_key)
        if share > self.budget_bytes:
            self.oom_refusals += 1
            raise DeviceBudgetError(
                f"store needs ~{share >> 20} MiB per device but the "
                f"device budget is {self.budget_bytes >> 20} MiB "
                f"(SURREAL_DEVICE_MEM_BUDGET_MB)"
            )
        while self.mem_used_device0() + share > self.budget_bytes:
            victim = None
            for cache in (self.csr, self.ft, self.vec, self.ann):
                for key in cache:
                    if key != keep_key:
                        victim = (cache, key)
                        break
                if victim is not None:
                    break
            if victim is None:
                self.oom_refusals += 1
                raise DeviceBudgetError(
                    f"store needs ~{share >> 20} MiB per device; "
                    f"{self.mem_used_device0() >> 20} MiB resident is "
                    f"unevictable (staging) under the "
                    f"{self.budget_bytes >> 20} MiB budget"
                )
            victim[0].pop(victim[1], None)
            self.budget_evictions += 1

    # -- ops ----------------------------------------------------------------
    def handle(self, op: str, meta: dict, bufs: list):
        fn = getattr(self, f"op_{op}", None)
        if fn is None:
            raise ValueError(f"unknown device op {op!r}")
        out = fn(meta, bufs)
        if out[0] == "ok":
            self.op_counts[op] = self.op_counts.get(op, 0) + 1
        return out

    def op_ping(self, meta, bufs):
        return "ok", {}, []

    def op_status(self, meta, bufs):
        import jax

        from surrealdb_tpu.device import compile_cache, kernelstats
        from surrealdb_tpu.device import mesh as devmesh

        def _sharded(cache):
            return sum(1 for _t, s in cache.values()
                       if _store_ndev(s) > 1)

        devs = jax.devices()
        per_device = []
        for d in devs:
            # None where the backend keeps no allocator stats (cpu)
            ms = d.memory_stats() or {}
            per_device.append({
                "id": d.id,
                "bytes_in_use": ms.get("bytes_in_use"),
                "peak_bytes_in_use": ms.get("peak_bytes_in_use"),
                "bytes_limit": ms.get("bytes_limit"),
            })
        cdir = jax.config.jax_compilation_cache_dir
        return "ok", {
            "platform": devs[0].platform if devs else "none",
            "device_kind": devs[0].device_kind if devs else None,
            "device_count": len(devs),
            "devices": per_device,
            "ops": dict(self.op_counts),
            "rank_modes": sorted({str(s.rank_mode)
                                  for _t, s in self.vec.values()}),
            "mesh": dict(devmesh.describe(),
                         sharded_vec=_sharded(self.vec),
                         sharded_ann=_sharded(self.ann),
                         sharded_csr=_sharded(self.csr)),
            "mem_used_device0": self.mem_used_device0(),
            "vec_blocks": len(self.vec),
            "csr_blocks": len(self.csr),
            "ann_blocks": len(self.ann),
            "ft_blocks": len(self.ft),
            "vec_bytes": sum(s.nbytes() for _t, s in self.vec.values()),
            "csr_bytes": sum(s.nbytes() for _t, s in self.csr.values()),
            "ann_bytes": sum(s.nbytes() for _t, s in self.ann.values()),
            "mem_used": self.mem_used(),
            "mem_budget": self.budget_bytes,
            "oom_refusals": self.oom_refusals,
            "budget_evictions": self.budget_evictions,
            "compile_cache": {
                "dir": cdir, "entries": compile_cache.entry_count(cdir),
            } if cdir else {"disabled": "unset"},
            "compile": dict(
                kernelstats.COMPILE, backend_compile_s=dict(
                    kernelstats.COMPILE["backend_compile_s"])),
            "cc": kernelstats.snapshot(),
            # the serve loop's idle/busy nanoseconds and the ANN
            # descents' row counts (kernelstats.LOOP / ANN)
            "loop": kernelstats.loop_snapshot(),
            "ann": dict(kernelstats.ANN),
            # the bag hops' riders, paths and overflows (kernelstats.CSR)
            "csr": dict(kernelstats.CSR),
            # the exact stores' scans (kernelstats.SCAN)
            "scan": dict(kernelstats.SCAN),
            # the growing stores: rows held and rows allocated for, a
            # store, and what `vec_append` wrote (kernelstats.APPEND)
            "vec": {key: {"rows": s.n, "capacity": s.capacity}
                    for key, (_t, s) in self.vec.items() if s.growable},
            "append": dict(kernelstats.APPEND),
            # the graph builds' kNN passes (kernelstats.GRAPH), and the
            # builds whose rows are on the device now
            "graph": dict(kernelstats.GRAPH, open=len(self._graphs)),
            # the BM25 top-n riders over full-text blocks (kernelstats.FT)
            "ft": dict(kernelstats.FT),
        }, []

    def op_profile(self, meta, bufs):
        """A profiler window the program owns (DeviceSupervisor.profile):
        `start` opens jax's trace into `dir`, `stop` closes and writes
        it. Only this process holds the chip, so only it can trace it;
        the runner's `runner:*` spans (kernelstats.phase, runner.serve)
        land in the same file as the device's operations. The Python
        function tracer stays off: it would slow the very host work the
        spans are there to time, and it is most of what `stop_trace`
        has to write."""
        import jax

        if meta["action"] == "start":
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(meta["dir"], profiler_options=opts)
            return "ok", {"started": time.monotonic()}, []
        t0 = time.monotonic()
        jax.profiler.stop_trace()
        return "ok", {"stopping": t0, "stopped": time.monotonic()}, []

    def op_vec_load(self, meta, bufs):
        key = meta["key"]
        vecs, valid = bufs
        ndev = self._place_vec(vecs.shape[0], vecs.shape[1],
                               vecs.dtype.itemsize, meta)
        self._admit(
            _vec_estimate(vecs.shape[0], vecs.shape[1],
                          vecs.dtype.itemsize, meta, ndev),
            keep_key=key, ndev=max(ndev, 1),
        )
        st = self._vec_store(key, vecs, valid, meta, ndev)
        st.ensure()
        self.vec.pop(key, None)
        self.vec[key] = (list(meta["tag"]), st)
        while len(self.vec) > MAX_VEC_STORES:
            self.vec.popitem(last=False)
        return "ok", {"rank_mode": st.rank_mode,
                      "mesh_ndev": _store_ndev(st)}, []

    @staticmethod
    def _vec_store(key, vecs, valid, meta, ndev: int):
        """Placed construction: a MeshVecStore on a mesh runner, the
        legacy VecStore otherwise (mesh off / one device)."""
        if ndev:
            from surrealdb_tpu.device.mesh import MeshVecStore

            return MeshVecStore(key, vecs, valid, meta["metric"],
                                meta.get("mink_p", 3.0), meta["cfg"],
                                ndev)
        from surrealdb_tpu.device.vecstore import VecStore

        return VecStore(key, vecs, valid, meta["metric"],
                        meta.get("mink_p", 3.0), meta["cfg"])

    def op_vec_load_begin(self, meta, bufs):
        key = meta["key"]
        n, dim = meta["shape"]
        dtype = np.dtype(meta["dtype"])
        # admit staging + the final device arrays up front, BEFORE the
        # big allocation: both are alive while load_end ensures the
        # store, a refusal must land while the runner is still cheap
        # to answer from, and the install share stays RESERVED (so a
        # concurrent ship admitted mid-stream cannot overcommit) until
        # load_end installs the store
        ndev = self._place_vec(int(n), int(dim), dtype.itemsize, meta)
        est = _vec_estimate(int(n), int(dim), dtype.itemsize, meta,
                            ndev)
        share = -(-est // max(ndev, 1))
        # staging is a host-side buffer: it occupies the runner whole,
        # the install share is what lands per device
        self._admit_share(
            int(n) * int(dim) * dtype.itemsize + int(n) + share,
            keep_key=key,
        )
        self._reserved.pop(key, None)
        if self.budget_bytes > 0:
            self._reserved[key] = share
        vecs = np.empty((int(n), int(dim)), dtype=dtype)
        (valid,) = bufs
        lmeta = dict(meta)
        lmeta["_mesh_ndev"] = ndev
        self._staging[key] = (lmeta, vecs, valid)
        return "ok", {}, []

    def op_vec_load_part(self, meta, bufs):
        ent = self._staging.get(meta["key"])
        if ent is None:
            return "stale", {}, []
        _m, vecs, _valid = ent
        off = int(meta["off"])
        (chunk,) = bufs
        vecs[off:off + chunk.shape[0]] = chunk
        return "ok", {}, []

    def op_vec_load_end(self, meta, bufs):
        key = meta["key"]
        ent = self._staging.pop(key, None)
        self._reserved.pop(key, None)  # the install replaces it below
        if ent is None:
            return "stale", {}, []
        lmeta, vecs, valid = ent
        st = self._vec_store(key, vecs, valid, lmeta,
                             int(lmeta.get("_mesh_ndev", 0)))
        st.ensure()
        self.vec.pop(key, None)
        self.vec[key] = (list(meta["tag"]), st)
        while len(self.vec) > MAX_VEC_STORES:
            self.vec.popitem(last=False)
        return "ok", {"rank_mode": st.rank_mode,
                      "mesh_ndev": _store_ndev(st)}, []

    def op_vec_drop(self, meta, bufs):
        self.vec.pop(meta["key"], None)
        self._staging.pop(meta["key"], None)
        self._reserved.pop(meta["key"], None)
        self._drop_graph(meta["key"])  # a graph build given up
        return "ok", {}, []

    def op_vec_knn(self, meta, bufs):
        ent = self.vec.get(meta["key"])
        if ent is None or ent[0] != list(meta["tag"]):
            return "stale", {}, []
        self.vec.move_to_end(meta["key"])
        out_meta, out_bufs = ent[1].knn(bufs[0], int(meta["k"]))
        out_meta.setdefault("mesh_ndev", _store_ndev(ent[1]))
        if ent[1].growable:
            # tells the serving side that a delta will do next time,
            # and up to which row
            out_meta["capacity"] = ent[1].capacity
        if out_meta.get("rank_mode") is None:
            # an exact store scored every row for every rider
            from surrealdb_tpu.device.kernelstats import SCAN

            riders = bufs[0].shape[0]
            SCAN["riders"] += riders
            SCAN["dispatches"] += 1
            SCAN["rows_scored"] += riders * ent[1].shape[0]
        return "ok", out_meta, out_bufs

    def op_vec_append(self, meta, bufs):
        """A delta for a resident block that grows in place
        (device/vecstore.py `append`): rows [m, D], their row numbers
        and mask bits, from tag `tag_from` to `tag`. `stale` where the
        runner does not hold `tag_from` (evicted, restarted, another
        delta went first), `full` where the store does not grow in
        place or the rows pass its capacity: either way the caller
        ships the whole block, and that is the only time a write costs
        a re-ship and new programs."""
        key = meta["key"]
        ent = self.vec.get(key)
        if ent is None or ent[0] != list(meta["tag_from"]):
            return "stale", {}, []
        rows, row_numbers, flags = bufs
        st = ent[1]
        if not (st.growable and st.append(rows, row_numbers, flags)):
            return "full", {}, []
        self.vec[key] = (list(meta["tag"]), st)
        self.vec.move_to_end(key)
        from surrealdb_tpu.device.kernelstats import APPEND

        APPEND["appends"] += 1
        APPEND["rows"] += int(len(row_numbers))
        APPEND["bytes"] += sum(int(b.nbytes) for b in bufs)
        return "ok", {"rows": st.n, "capacity": st.capacity}, []

    def _prewarm_shapes(self, cache, meta, field, warm_one):
        """Shared prewarm skeleton: compile one kernel shape per listed
        step for a loaded block AHEAD of traffic (runner start / store
        re-ship), so serving queries never pay an XLA compile mid-query.
        With the persistent compile cache warm this is a handful of
        disk loads. Best-effort by contract — a failed shape stops the
        ladder but never fails serving; a dropped/re-tagged block is
        `stale`."""
        ent = cache.get(meta["key"])
        if ent is None or ent[0] != list(meta["tag"]):
            return "stale", {}, []
        warmed = []
        for v in meta.get(field, (1,)):
            v = int(v)
            if v < 1:
                continue
            try:
                warm_one(ent[1], v)
                warmed.append(v)
            except Exception:
                break
        return "ok", {"warmed": warmed}, []

    def op_vec_prewarm(self, meta, bufs):
        """Power-of-two query-bucket ladder for a vector store."""
        k = int(meta.get("k", 10))

        def warm(st, b):
            st.knn(np.zeros((b, st.shape[1]), np.float32), k)
            # a store that grows in place: the append programs of every
            # ladder step up to `b` rows (warming is idempotent: a step
            # met before is a counted hit)
            step = 1
            while step <= b and st.growable:
                st.warm_append(step)
                step *= 2

        return self._prewarm_shapes(self.vec, meta, "buckets", warm)

    # -- quantized graph-ANN blocks (device/annstore.py) --------------------

    def _ann_install(self, key, tag, meta, graph, x8, arow, x2q):
        ndev = self._place_ann(x8.shape[0], x8.shape[1], graph.shape[1])
        if ndev:
            from surrealdb_tpu.device.mesh import MeshAnnStore

            self._admit(MeshAnnStore.estimate_device_bytes(
                x8.shape[0], x8.shape[1], graph.shape[1], ndev
            ), keep_key=key, ndev=ndev)
            st = MeshAnnStore(key, graph, x8, arow, x2q,
                              meta["metric"], meta.get("cfg") or {},
                              ndev)
        else:
            from surrealdb_tpu.device.annstore import AnnStore

            self._admit(AnnStore.estimate_device_bytes(
                x8.shape[0], x8.shape[1], graph.shape[1]
            ), keep_key=key)
            st = AnnStore(key, graph, x8, arow, x2q, meta["metric"],
                          meta.get("cfg") or {})
        st._ensure()
        self.ann.pop(key, None)
        self.ann[key] = (list(tag), st)
        while len(self.ann) > MAX_ANN_STORES:
            self.ann.popitem(last=False)
        return "ok", {"mesh_ndev": _store_ndev(st)}, []

    def op_ann_load(self, meta, bufs):
        graph, x8, arow, x2q = bufs
        return self._ann_install(meta["key"], meta["tag"], meta,
                                 graph, x8, arow, x2q)

    def op_ann_load_begin(self, meta, bufs):
        from surrealdb_tpu.device.annstore import AnnStore

        key = meta["key"]
        arow, x2q = bufs
        n = arow.shape[0]
        # staging + installed arrays coexist briefly at load_end; the
        # install share stays reserved until then so concurrent ships
        # cannot overcommit between begin and end
        ndev = self._place_ann(n, int(meta["dim"]), int(meta["d_out"]))
        est = AnnStore.estimate_device_bytes(
            n, int(meta["dim"]), int(meta["d_out"])
        )
        share = -(-est // max(ndev, 1))
        # host staging (≈ est) occupies the runner whole; the install
        # share is per device once _ann_install places the mesh store
        self._admit_share(est + share, keep_key=key)
        self._reserved.pop(key, None)
        if self.budget_bytes > 0:
            self._reserved[key] = share
        bufs_by_name = {
            "graph": np.empty((n, int(meta["d_out"])), np.int32),
            "x8": np.empty((n, int(meta["dim"])), np.int8),
            "arow": arow,
            "x2q": x2q,
        }
        self._ann_staging[key] = (dict(meta), bufs_by_name)
        return "ok", {}, []

    def op_ann_load_part(self, meta, bufs):
        ent = self._ann_staging.get(meta["key"])
        if ent is None:
            return "stale", {}, []
        target = ent[1][meta["buf"]]
        off = int(meta["off"])
        (chunk,) = bufs
        target[off:off + chunk.shape[0]] = chunk
        return "ok", {}, []

    def op_ann_load_end(self, meta, bufs):
        key = meta["key"]
        ent = self._ann_staging.pop(key, None)
        self._reserved.pop(key, None)  # _ann_install re-admits below
        if ent is None:
            return "stale", {}, []
        lmeta, by_name = ent
        return self._ann_install(
            key, meta["tag"], lmeta, by_name["graph"], by_name["x8"],
            by_name["arow"], by_name["x2q"],
        )

    def op_ann_drop(self, meta, bufs):
        self.ann.pop(meta["key"], None)
        self._ann_staging.pop(meta["key"], None)
        self._reserved.pop(meta["key"], None)
        return "ok", {}, []

    def op_ann_search(self, meta, bufs):
        ent = self.ann.get(meta["key"])
        if ent is None or ent[0] != list(meta["tag"]):
            return "stale", {}, []
        self.ann.move_to_end(meta["key"])
        cand = ent[1].search(bufs[0], int(meta["kc"]))
        return "ok", {"mode": "cand",
                      "mesh_ndev": _store_ndev(ent[1])}, \
            [cand]

    def op_ann_knn_graph(self, meta, bufs):
        """One call of a graph build's kNN initialisation (idx/cagra.py
        build_graph): for the query chunks [c0, c0 + count) of `chunk`
        rows, each row's `keep` nearest other rows of the span staged
        under `key` (`vec_load_begin` / `vec_load_part`: its rows'
        bfloat16 bits [N, D] uint16, in the build space). The first call
        of a build moves the rows to the device and compiles the program
        (`compiling` announced: every build compiles anew, the program
        goes with it); both stay under the key, reserved, until the call
        with `last` or one that fails. A build takes many calls, each
        bounded (`DeviceSupervisor.knn_graph`), so searches are served
        between them. One packed array back (`ops/topk.py knn_graph`)."""
        import jax.numpy as jnp

        from surrealdb_tpu.device.kernelstats import (
            GRAPH, note_compile, note_hit, phase,
        )
        from surrealdb_tpu.ops.topk import (
            SCAN_BLOCK, knn_graph, knn_graph_costs,
        )

        key = meta["key"]
        keep, chunk = int(meta["keep"]), int(meta["chunk"])
        c0, count = int(meta["c0"]), int(meta["count"])
        try:
            ent = self._graphs.get(key)
            if ent is None:
                staged = self._staging.pop(key, None)
                self._reserved.pop(key, None)
                if staged is None:
                    return "stale", {}, []
                bits = staged[1]
                del staged
                with phase("h2d"):
                    dev = jnp.asarray(bits)
                if self.budget_bytes > 0:
                    self._reserved[key] = int(bits.nbytes)
                del bits
                note_compile("ann_knn_graph")
                with phase("device"):
                    prog = knn_graph.lower(
                        dev, np.int32(0), np.int32(0), keep=keep,
                        chunk=chunk, per_call=int(meta["per_call"]),
                        block=SCAN_BLOCK).compile()
                ent = self._graphs[key] = (dev, prog)
            else:
                note_hit("ann_knn_graph")
            dev, prog = ent
            with phase("device"):
                # `device_ns`: the launch until the output is on the host
                t0 = time.monotonic_ns()
                packed = np.asarray(prog(dev, np.int32(c0), np.int32(count)))
                GRAPH["device_ns"] += time.monotonic_ns() - t0
        except BaseException:
            self._drop_graph(key)
            raise
        n, dim = dev.shape
        ops, moved = knn_graph_costs(int(n), int(dim), keep, chunk, c0, count)
        GRAPH["calls"] += 1
        GRAPH["flops"] += ops
        GRAPH["bytes"] += moved
        if meta.get("last"):
            self._drop_graph(key)
            GRAPH["runs"] += 1
        return "ok", {}, [packed[:count * chunk]]

    def _drop_graph(self, key):
        """A build's kNN pass is over: its rows and program leave the
        device, and so does the jitted function's cache (builds are rare:
        nothing of one holds device memory while serving)."""
        from surrealdb_tpu.ops.topk import knn_graph

        self._reserved.pop(key, None)
        if self._graphs.pop(key, None) is not None and not self._graphs:
            knn_graph.clear_cache()

    def op_ann_prewarm(self, meta, bufs):
        """Query-bucket ladder for an ANN index's descent kernel."""
        kc = int(meta.get("kc", 40))

        def warm(st, b):
            st.search(np.zeros((b, st.x8.shape[1]), np.float32), kc)

        return self._prewarm_shapes(self.ann, meta, "buckets", warm)

    def op_csr_load(self, meta, bufs):
        key = meta["key"]
        rows, cols = bufs
        ndev = self._place_csr(rows.shape[0])
        if ndev:
            from surrealdb_tpu.device.mesh import MeshCsrStore

            self._admit(MeshCsrStore.estimate_device_bytes(
                rows.shape[0], ndev
            ), keep_key=key, ndev=ndev)
            st = MeshCsrStore(key, rows, cols, int(meta["n_nodes"]),
                              ndev)
        else:
            from surrealdb_tpu.device.csrstore import CsrStore

            self._admit(int(rows.nbytes) + int(cols.nbytes),
                        keep_key=key)
            st = CsrStore(key, rows, cols, int(meta["n_nodes"]))
        self.csr.pop(key, None)
        self.csr[key] = (list(meta["tag"]), st)
        while len(self.csr) > MAX_CSR_STORES:
            self.csr.popitem(last=False)
        return "ok", {}, []

    def op_csr_drop(self, meta, bufs):
        self.csr.pop(meta["key"], None)
        return "ok", {}, []

    def op_csr_hop(self, meta, bufs):
        ent = self.csr.get(meta["key"])
        if ent is None or ent[0] != list(meta["tag"]):
            return "stale", {}, []
        self.csr.move_to_end(meta["key"])
        mask = ent[1].multi_hop(
            bufs[0], int(meta["hops"]), bool(meta["union"])
        )
        return "ok", {"mesh_ndev": _store_ndev(ent[1])}, \
            [mask]

    def op_csr_bag_hop(self, meta, bufs):
        """A batch of folded `->edge->node` chains with BAG semantics
        (device/csrstore.py bag_hop): [B, 1 + C0] int32 riders in,
        every level's true total and the riders' last levels out. A
        store that lives on a mesh has no bag kernel and says so
        (`refused`): the serving side walks its host CSR and counts the
        query as host-routed."""
        ent = self.csr.get(meta["key"])
        if ent is None or ent[0] != list(meta["tag"]):
            return "stale", {}, []
        self.csr.move_to_end(meta["key"])
        if not hasattr(ent[1], "bag_hop"):
            return "refused", {"mesh_ndev": _store_ndev(ent[1])}, []
        totals, flat = ent[1].bag_hop(bufs[0], meta["caps"])
        return "ok", {}, [totals, flat]

    def op_csr_prewarm(self, meta, bufs):
        """Hop-depth ladder for a CSR graph: the first `->edge->`
        expansion after a ship/restart must not pay an XLA compile
        mid-query (the sql_graph_3hop bench measured 11.4 s of
        first-query tax). For each depth the set hop's two programs
        and the bag hop's first rung from one start node, at every
        rider bucket up to `BAG_PREWARM_RIDERS`."""
        from surrealdb_tpu.device.csrstore import (
            BAG_PREWARM_RIDERS, bag_caps,
        )

        def warm(st, hops):
            start = np.zeros((1, st.n_nodes), np.uint8)
            for union in (False, True):
                st.multi_hop(start, hops, union)
            if not hasattr(st, "bag_hop"):
                return
            ladder = bag_caps(st.n_nodes, len(st.cols), 1, hops)
            b = 1
            while ladder and b <= BAG_PREWARM_RIDERS:
                st.bag_hop(np.zeros((b, 2), np.int32), ladder[0])
                b *= 2

        return self._prewarm_shapes(self.csr, meta, "hops", warm)

    # -- full-text blocks (device/ftstore.py) ---------------------------------

    def op_ft_load(self, meta, bufs):
        """One index version's postings: `indptr`, `docs`, `tf`, `dl`
        (int32), resident until the tag moves or the cache evicts it."""
        from surrealdb_tpu.device.ftstore import FtStore

        key = meta["key"]
        st = FtStore(key, *bufs)
        self._admit(st.device_nbytes(), keep_key=key)
        st._ensure()
        self.ft.pop(key, None)
        self.ft[key] = (list(meta["tag"]), st)
        while len(self.ft) > MAX_FT_STORES:
            self.ft.popitem(last=False)
        return "ok", {"rungs": list(st.rungs)}, []

    def op_ft_bm25(self, meta, bufs):
        """A batch of BM25 riders (device/ftstore.py): one int32 array
        of riders a group, meta `groups` [[capacity, k], ...] beside them
        and `consts` (k1, 1 - b, b / avg_len) of the store's version; one
        packed int32 array back a group."""
        ent = self.ft.get(meta["key"])
        if ent is None or ent[0] != list(meta["tag"]):
            return "stale", {}, []
        self.ft.move_to_end(meta["key"])
        return "ok", {}, [
            ent[1].bm25(riders, meta["consts"], int(cap), int(k))
            for riders, (cap, k) in zip(bufs, meta["groups"])]

    def op_ft_prewarm(self, meta, bufs):
        """The program of one shape (`cap`, `riders`, `terms`, `k`) of a
        resident full-text block, compiled ahead of traffic."""
        ent = self.ft.get(meta["key"])
        if ent is None or ent[0] != list(meta["tag"]):
            return "stale", {}, []
        ent[1].warm(int(meta["terms"]), int(meta["k"]), int(meta["cap"]),
                    int(meta["riders"]))
        return "ok", {}, []

    def op_brute_knn(self, meta, bufs):
        """One-shot exact KNN over ephemeral rows (planner brute path —
        nothing cached; xs ships with the call)."""
        import jax.numpy as jnp

        from surrealdb_tpu.ops.topk import knn_search

        xs, qs = bufs
        d, i = knn_search(
            jnp.asarray(xs), jnp.asarray(qs), int(meta["k"]),
            meta["metric"], float(meta.get("p", 3.0)),
        )
        return "ok", {}, [
            np.ascontiguousarray(np.asarray(d), np.float32),
            np.ascontiguousarray(np.asarray(i), np.int32),
        ]
