"""Sharded KNN over a device mesh.

RUNNER-SIDE ONLY: this module imports jax at module level, so it may
only be imported from the DeviceRunner subprocess (surrealdb_tpu.device
— which builds the mesh during vec_load), bench/tooling, or tests —
never from query-execution code (tools/check_robustness.py rule 5).

Vectors live row-sharded across devices ("data" axis). The production
multi-chip kernel is the SAME two-stage design as single-chip
(ops/topk.py knn_rank_rescore): each shard ranks its local rows with one
bf16 matmul (f32 accumulation) + `lax.approx_max_k`, then rescores its
OWN candidates exactly in f32 — the candidate gather never crosses
shards — and only the [B, kc] (dist, global-id) candidate tiles ride the
ICI `all_gather` before the final exact `top_k` merge. This is the
per-shard top-k + cross-shard merge called for in SURVEY.md §7 step 4,
replacing the reference's DoublePriorityQueue (idx/trees/knn.rs:15).
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
# the [B, kc, D] rescore contractions promise exact f32 distances: full
# f32 on the MXU, not the TPU's default single bf16 pass (ops/topk.py)
_EXACT = jax.lax.Precision.HIGHEST


def default_mesh(devices=None) -> Mesh:
    devices = devices if devices is not None else jax.devices()
    return Mesh(np.asarray(devices), (DATA_AXIS,))


def shard_rows(mesh: Mesh, arr):
    """Place a [N, D] array row-sharded over the mesh (pads N to shards)."""
    n_shards = mesh.devices.size
    n = arr.shape[0]
    pad = (-n) % n_shards
    if pad:
        arr = np.pad(arr, ((0, pad), (0, 0)))
    sharding = NamedSharding(mesh, P(DATA_AXIS, None))
    return jax.device_put(arr, sharding), pad


def shard_vec(mesh: Mesh, arr, pad: int, fill=0):
    """Place a [N] per-row array sharded to match shard_rows."""
    if pad:
        arr = np.pad(arr, (0, pad), constant_values=fill)
    return jax.device_put(arr, NamedSharding(mesh, P(DATA_AXIS)))


def _sharded_knn_impl(xs, qs, valid, k: int, metric: str, p: float):
    from surrealdb_tpu.ops.distance import distance_matrix

    d = distance_matrix(xs, qs, metric, p)
    d = jnp.where(valid[None, :], d, jnp.inf)
    nd, ni = jax.lax.top_k(-d, k)
    return -nd, ni


@lru_cache(maxsize=64)
def _sharded_knn_jit(mesh: Mesh):
    # jit cache keyed on the mesh (Mesh is hashable): building a fresh
    # jax.jit per call would retrace + recompile on the hot path
    out_shard = NamedSharding(mesh, P(None, None))
    return jax.jit(
        _sharded_knn_impl,
        static_argnames=("k", "metric"),
        out_shardings=(out_shard, out_shard),
    )


def sharded_knn(mesh: Mesh, xs_sharded, qs, valid, k: int,
                metric: str = "euclidean", p: float = 3.0):
    """Exact f32/f64 fused distance+top-k on row-sharded vectors (the
    non-MXU metrics). XLA partitions the distance kernel over the data
    axis and inserts the cross-shard top-k merge."""
    qs_rep = jax.device_put(qs, NamedSharding(mesh, P(None, None)))
    return _sharded_knn_jit(mesh)(xs_sharded, qs_rep, valid, k, metric, p)


def _rank_rescore_shard(xr, xf, x2, norms, valid, qs, k: int, kc: int,
                        metric: str, recall_target: float):
    """Per-shard body (runs inside shard_map): local bf16 rank →
    approx_max_k(kc) → LOCAL exact f32 rescore → all_gather the candidate
    tiles over ICI → exact global top-k. Row ids are globalized with the
    shard offset so the merged ids index the unsharded store."""
    base = jax.lax.axis_index(DATA_AXIS) * xr.shape[0]
    qb = qs.astype(jnp.bfloat16)
    dots = jnp.einsum("nd,bd->bn", xr, qb, preferred_element_type=jnp.float32)
    if metric == "euclidean":
        score = x2[None, :] - 2.0 * dots
    else:  # cosine (pre-normalized rank rows) / dot
        score = -dots
    score = jnp.where(valid[None, :], score, jnp.inf)
    _, cand = jax.lax.approx_max_k(-score, kc, recall_target=recall_target)
    rows = xf[cand]  # [B, kc, D] — gather stays inside the shard
    if metric == "euclidean":
        diff = rows - qs[:, None, :]
        d = jnp.sqrt(jnp.maximum((diff * diff).sum(axis=-1), 0.0))
    elif metric == "cosine":
        dd = jnp.einsum("bkd,bd->bk", rows, qs, precision=_EXACT,
                        preferred_element_type=jnp.float32)
        qn = jnp.maximum(jnp.linalg.norm(qs, axis=-1), 1e-30)
        d = 1.0 - dd / jnp.maximum(norms[cand] * qn[:, None], 1e-30)
    else:  # dot
        d = -jnp.einsum("bkd,bd->bk", rows, qs, precision=_EXACT,
                        preferred_element_type=jnp.float32)
    d = jnp.where(valid[cand], d, jnp.inf)
    gids = (cand + base).astype(jnp.int32)
    # merge: only [B, kc] candidate tiles cross ICI, never distance rows
    d_all = jax.lax.all_gather(d, DATA_AXIS, axis=1, tiled=True)
    i_all = jax.lax.all_gather(gids, DATA_AXIS, axis=1, tiled=True)
    nd, sel = jax.lax.top_k(-d_all, k)
    return -nd, jnp.take_along_axis(i_all, sel, axis=1)


@lru_cache(maxsize=256)
def _rank_rescore_jit(mesh: Mesh, k: int, kc: int, metric: str,
                      recall_target: float):
    # jit cache keyed on (mesh, k, kc, metric, recall_target): a fresh
    # jit(shard_map(partial(...))) per call defeats jit's trace cache and
    # pays full XLA compile on every query batch (~150x on the hot path)
    return jax.jit(
        jax.shard_map(
            partial(_rank_rescore_shard, k=k, kc=kc, metric=metric,
                    recall_target=recall_target),
            mesh=mesh,
            in_specs=(P(DATA_AXIS, None), P(DATA_AXIS, None), P(DATA_AXIS),
                      P(DATA_AXIS), P(DATA_AXIS), P(None, None)),
            out_specs=(P(None, None), P(None, None)),
            # outputs are identical on every shard after the all_gather +
            # top_k merge; the static VMA check can't see through top_k
            check_vma=False,
        )
    )


def sharded_rank_rescore(mesh: Mesh, xs_rank, xs_full, qs, k: int, kc: int,
                         metric: str = "euclidean", x2=None, norms=None,
                         valid=None, recall_target: float = 0.95):
    """Two-stage sharded KNN for the MXU metrics (euclidean/cosine/dot) —
    the production multi-chip path, same kernel design the single-chip
    index uses (ops/topk.py knn_rank_rescore). All [N,*] inputs must be
    row-sharded over `mesh`'s data axis (shard_rows/shard_vec); `qs` is
    [B, D] f32, replicated. Returns (dists [B, k] f32, ids [B, k] i32)
    replicated."""
    nloc = xs_rank.shape[0] // mesh.devices.size
    if x2 is None:
        x2 = jnp.zeros((xs_rank.shape[0],), dtype=jnp.float32)
    if norms is None:
        norms = jnp.ones((xs_rank.shape[0],), dtype=jnp.float32)
    if valid is None:
        valid = jnp.ones((xs_rank.shape[0],), dtype=bool)
    kc = min(kc, nloc)
    k = min(k, kc * mesh.devices.size)
    qs_rep = jax.device_put(
        np.ascontiguousarray(qs, dtype=np.float32),
        NamedSharding(mesh, P(None, None)),
    )
    fn = _rank_rescore_jit(mesh, k, kc, metric, recall_target)
    return fn(xs_rank, xs_full, x2, norms, valid, qs_rep)


# ---------------------------------------------------------------------------
# multi-host (DCN) meshes
# ---------------------------------------------------------------------------

DCN_AXIS = "dcn"


def multihost_mesh(devices=None, hosts: int | None = None) -> Mesh:
    """Two-axis (dcn, data) mesh for multi-host deployments: the host
    axis rides DCN, the per-host device axis rides ICI (SURVEY §2.13
    TPU-equivalents; "How to Scale Your Model" hybrid-mesh recipe).

    Under real multi-process JAX, devices group by process via
    `mesh_utils.create_hybrid_device_mesh` so each mesh row is one
    host's ICI domain. In a single process (the dryrun validator),
    `hosts` splits the local devices into simulated host groups — the
    collective STRUCTURE (ICI-stage merge, then DCN-stage merge) is
    identical, only the transport differs."""
    devices = list(devices if devices is not None else jax.devices())
    nproc = jax.process_count()
    if hosts is None:
        hosts = nproc
    if hosts <= 1:
        return Mesh(np.asarray(devices).reshape(1, -1),
                    (DCN_AXIS, DATA_AXIS))
    if nproc > 1 and hosts == nproc:
        from jax.experimental import mesh_utils

        arr = mesh_utils.create_hybrid_device_mesh(
            (len(devices) // hosts,), (hosts,), devices=devices,
        )
        return Mesh(arr.reshape(hosts, -1), (DCN_AXIS, DATA_AXIS))
    if len(devices) % hosts:
        raise ValueError(
            f"{len(devices)} devices do not split into {hosts} hosts"
        )
    return Mesh(np.asarray(devices).reshape(hosts, -1),
                (DCN_AXIS, DATA_AXIS))


def shard_rows_hier(mesh: Mesh, arr):
    """Row-shard a [N, D] array over BOTH mesh axes (host-major)."""
    n_shards = mesh.devices.size
    pad = (-arr.shape[0]) % n_shards
    if pad:
        arr = np.pad(arr, ((0, pad), (0, 0)))
    return jax.device_put(
        arr, NamedSharding(mesh, P((DCN_AXIS, DATA_AXIS), None))
    ), pad


def shard_vec_hier(mesh: Mesh, arr, pad: int, fill=0):
    if pad:
        arr = np.pad(arr, (0, pad), constant_values=fill)
    return jax.device_put(
        arr, NamedSharding(mesh, P((DCN_AXIS, DATA_AXIS)))
    )


def _rank_rescore_shard_hier(xr, xf, x2, norms, valid, qs, k: int, kc: int,
                             metric: str, recall_target: float):
    """Hierarchical merge: candidates all_gather + top-k over the ICI
    axis first (intra-host), then only the per-host [B, k] winners cross
    the DCN axis for the final merge — the expensive inter-host hop
    carries k candidates per host, not kc x devices."""
    ici_sz = jax.lax.axis_size(DATA_AXIS)
    base = (
        jax.lax.axis_index(DCN_AXIS) * ici_sz
        + jax.lax.axis_index(DATA_AXIS)
    ) * xr.shape[0]
    qb = qs.astype(jnp.bfloat16)
    dots = jnp.einsum("nd,bd->bn", xr, qb, preferred_element_type=jnp.float32)
    if metric == "euclidean":
        score = x2[None, :] - 2.0 * dots
    else:
        score = -dots
    score = jnp.where(valid[None, :], score, jnp.inf)
    _, cand = jax.lax.approx_max_k(-score, kc, recall_target=recall_target)
    rows = xf[cand]
    if metric == "euclidean":
        diff = rows - qs[:, None, :]
        d = jnp.sqrt(jnp.maximum((diff * diff).sum(axis=-1), 0.0))
    elif metric == "cosine":
        dd = jnp.einsum("bkd,bd->bk", rows, qs, precision=_EXACT,
                        preferred_element_type=jnp.float32)
        qn = jnp.maximum(jnp.linalg.norm(qs, axis=-1), 1e-30)
        d = 1.0 - dd / jnp.maximum(norms[cand] * qn[:, None], 1e-30)
    else:
        d = -jnp.einsum("bkd,bd->bk", rows, qs, precision=_EXACT,
                        preferred_element_type=jnp.float32)
    d = jnp.where(valid[cand], d, jnp.inf)
    gids = (cand + base).astype(jnp.int32)
    # stage 1: intra-host (ICI) merge
    d_ici = jax.lax.all_gather(d, DATA_AXIS, axis=1, tiled=True)
    i_ici = jax.lax.all_gather(gids, DATA_AXIS, axis=1, tiled=True)
    nd, sel = jax.lax.top_k(-d_ici, min(k, d_ici.shape[1]))
    d_host = -nd
    i_host = jnp.take_along_axis(i_ici, sel, axis=1)
    # stage 2: inter-host (DCN) merge — [B, k] per host only
    d_all = jax.lax.all_gather(d_host, DCN_AXIS, axis=1, tiled=True)
    i_all = jax.lax.all_gather(i_host, DCN_AXIS, axis=1, tiled=True)
    nd2, sel2 = jax.lax.top_k(-d_all, k)
    return -nd2, jnp.take_along_axis(i_all, sel2, axis=1)


@lru_cache(maxsize=256)
def _rank_rescore_hier_jit(mesh: Mesh, k: int, kc: int, metric: str,
                           recall_target: float):
    spec_rows = P((DCN_AXIS, DATA_AXIS), None)
    spec_vec = P((DCN_AXIS, DATA_AXIS))
    return jax.jit(
        jax.shard_map(
            partial(_rank_rescore_shard_hier, k=k, kc=kc, metric=metric,
                    recall_target=recall_target),
            mesh=mesh,
            in_specs=(spec_rows, spec_rows, spec_vec, spec_vec, spec_vec,
                      P(None, None)),
            out_specs=(P(None, None), P(None, None)),
            check_vma=False,
        )
    )


def sharded_rank_rescore_hier(mesh: Mesh, xs_rank, xs_full, qs, k: int,
                              kc: int, metric: str = "euclidean", x2=None,
                              norms=None, valid=None,
                              recall_target: float = 0.95):
    """Two-stage sharded KNN over a (dcn, data) hybrid mesh. Inputs are
    row-sharded over both axes (shard_rows_hier); outputs replicate."""
    nloc = xs_rank.shape[0] // mesh.devices.size
    if x2 is None:
        x2 = jnp.zeros((xs_rank.shape[0],), dtype=jnp.float32)
    if norms is None:
        norms = jnp.ones((xs_rank.shape[0],), dtype=jnp.float32)
    if valid is None:
        valid = jnp.ones((xs_rank.shape[0],), dtype=bool)
    kc = min(kc, nloc)
    k = min(k, kc * mesh.devices.shape[1])
    qs_rep = jax.device_put(
        np.ascontiguousarray(qs, dtype=np.float32),
        NamedSharding(mesh, P(None, None)),
    )
    fn = _rank_rescore_hier_jit(mesh, k, kc, metric, recall_target)
    return fn(xs_rank, xs_full, x2, norms, valid, qs_rep)
