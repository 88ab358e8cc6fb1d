"""Top-k selection kernels (replaces the reference's DoublePriorityQueue,
idx/trees/knn.rs:15, with `jax.lax.top_k` over batched distances)."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

# f32 contractions that promise exact results (the [B, kc, D] rescore):
# full f32 on the MXU instead of the TPU's default single bf16 pass
_EXACT = jax.lax.Precision.HIGHEST
# rows a step of `knn_search_blocked` scores: [B, SCAN_BLOCK] f32 is
# what the exact store's batches are sized against (device/vecstore.py)
SCAN_BLOCK = 65536


def pack_pairs(dists, ids):
    """(f32 dists, i32 ids), both [..., k], as ONE int32 array [..., 2k]
    built inside the jitted program: the distances' bits beside the ids,
    so that a dispatch copies one array back to the host and not two
    (each copy of a small ready array is a round trip to the chip).
    int32 and not f32 is the carrier: integer lanes are never
    canonicalised, where an id's bits read as f32 could be a NaN.
    `unpack_pairs` is the host's side."""
    return jnp.concatenate(
        [jax.lax.bitcast_convert_type(dists, jnp.int32),
         ids.astype(jnp.int32)], axis=-1,
    )


def unpack_pairs(packed):
    """A numpy `pack_pairs` array as (dists f32, ids i32): two views,
    bit for bit what the program computed."""
    k = packed.shape[-1] // 2
    return packed[..., :k].view(np.float32), packed[..., k:]


@partial(jax.jit, static_argnames=("k",))
def top_k_smallest(dists, k: int):
    """dists: [B, N] -> (values [B,k], indices [B,k]) of the k smallest."""
    neg, idx = jax.lax.top_k(-dists, k)
    return -neg, idx


@partial(jax.jit, static_argnames=("k", "metric", "packed"))
def knn_search(xs, qs, k: int, metric: str = "euclidean", p: float = 3.0,
               valid=None, packed: bool = False):
    """Fused distance + top-k. `valid`: optional [N] bool mask (tombstones /
    predicate pushdown); invalid rows get +inf distance. Returns
    (dists [B, k], ids [B, k]), or with `packed` (the served path,
    device/vecstore.py) the one `pack_pairs` array [B, 2k]."""
    from surrealdb_tpu.ops.distance import distance_matrix

    d = distance_matrix(xs, qs, metric, p)
    if valid is not None:
        d = jnp.where(valid[None, :], d, jnp.inf)
    out = top_k_smallest(d, k)
    return pack_pairs(*out) if packed else out


@partial(jax.jit, static_argnames=("k", "metric", "recall_target"))
def knn_rank_approx(xs, qs_r, k: int, metric: str = "euclidean",
                    x2=None, valid=None, recall_target: float = 0.95):
    """Primary single-chip candidate-ranking kernel for the MXU metrics
    (euclidean/cosine/dot).

    `xs` is the bfloat16 store ([N, D]; pre-normalized rows for cosine);
    `qs_r` is [R, B, D] f32 — R query batches ranked in ONE dispatch
    (amortizes the host→device round trip). Ranking scores are one bf16
    matmul per batch with f32 accumulation — for euclidean, |x|²-2x·q
    (monotonic in the true distance; `x2` carries precomputed f32 row
    norms). Top-k selection uses `lax.approx_max_k`, which lowers to
    the TPU PartialReduce op, with recall absorbed by caller-side
    oversampling + exact f32 rescoring (idx/vector.py). Returns
    candidate indices [R, B, k].

    Reference hot loop this replaces: idx/trees/hnsw/layer.rs:184-223
    (per-neighbor async KV fetch + scalar distance).
    """
    n = xs.shape[0]
    if valid is None:
        valid = jnp.ones((n,), dtype=bool)
    if x2 is None:
        x2 = jnp.zeros((n,), dtype=jnp.float32)

    def one(qs):
        qb = qs.astype(jnp.bfloat16)
        dots = jnp.einsum(
            "nd,bd->bn", xs, qb, preferred_element_type=jnp.float32
        )
        if metric == "euclidean":
            score = x2[None, :] - 2.0 * dots
        else:  # cosine (pre-normalized rows) and dot: higher dot = closer
            score = -dots
        score = jnp.where(valid[None, :], score, jnp.inf)
        _, idx = jax.lax.approx_max_k(
            -score, k, recall_target=recall_target
        )
        return idx

    return jax.lax.map(one, qs_r)


@partial(jax.jit, static_argnames=("k", "kc", "metric", "recall_target"))
def knn_rank_rescore(xs_rank, xs_full, qs_r, k: int, kc: int,
                     metric: str = "euclidean", x2=None, norms=None,
                     valid=None, recall_target: float = 0.95):
    """Fused two-stage KNN for the MXU metrics — the primary single-chip
    kernel. Stage 1 ranks the whole store with one bf16 matmul per query
    chunk (f32 accumulation) + `lax.approx_max_k` (TPU PartialReduce),
    keeping `kc` oversampled candidates. Stage 2 gathers the candidates'
    f32 rows from `xs_full` and rescores them EXACTLY on device (f32
    distances, exact `lax.top_k` over kc) in place of a host-side numpy
    rescore.

    `qs_r` is [R, B, D] f32 query chunks; returns ONE int32 array
    [R, B, 2k], the f32 distances' bits beside the int32 ids
    (`pack_pairs`; `unpack_pairs` splits it on the host), so a dispatch
    is one program and one copy back. `x2`: f32 row norms² (euclidean ranking);
    `norms`: f32 row norms (cosine rescore). Precision note: the
    stage-1 ranking matmul runs at the MXU's bf16 rate on purpose; the
    stage-2 contractions over the kc candidates carry
    `Precision.HIGHEST`, because a TPU otherwise multiplies f32 operands
    in one bf16 pass and the reported distances (and the final order
    among near ties) would inherit bf16 error. Stage-2 distances are
    therefore f32-accurate and can differ from the reference's f64 in
    low-order digits; stores below KNN_DEVICE_MIN_ROWS take the host
    f64 path, which is what the conformance oracle exercises. Reference
    hot loop replaced: idx/trees/hnsw/layer.rs:184-223."""
    n = xs_rank.shape[0]
    if valid is None:
        valid = jnp.ones((n,), dtype=bool)
    if x2 is None:
        x2 = jnp.zeros((n,), dtype=jnp.float32)
    if norms is None:
        norms = jnp.ones((n,), dtype=jnp.float32)

    def one(qs):
        qb = qs.astype(jnp.bfloat16)
        dots = jnp.einsum(
            "nd,bd->bn", xs_rank, qb, preferred_element_type=jnp.float32
        )
        if metric == "euclidean":
            score = x2[None, :] - 2.0 * dots
        else:  # cosine (pre-normalized rank rows) / dot
            score = -dots
        score = jnp.where(valid[None, :], score, jnp.inf)
        _, cand = jax.lax.approx_max_k(
            -score, kc, recall_target=recall_target
        )
        # stage 2: exact f32 rescore of the candidates, on device
        rows = xs_full[cand]  # [B, kc, D] dynamic gather
        if metric == "euclidean":
            diff = rows - qs[:, None, :]
            d = jnp.sqrt(jnp.maximum((diff * diff).sum(axis=-1), 0.0))
        elif metric == "cosine":
            dd = jnp.einsum(
                "bkd,bd->bk", rows, qs, precision=_EXACT,
                preferred_element_type=jnp.float32,
            )
            qn = jnp.maximum(jnp.linalg.norm(qs, axis=-1), 1e-30)
            d = 1.0 - dd / jnp.maximum(
                norms[cand] * qn[:, None], 1e-30
            )
        else:  # dot
            d = -jnp.einsum(
                "bkd,bd->bk", rows, qs, precision=_EXACT,
                preferred_element_type=jnp.float32,
            )
        d = jnp.where(valid[cand], d, jnp.inf)
        nd, sel = jax.lax.top_k(-d, k)
        ids = jnp.take_along_axis(cand, sel, axis=1)
        return pack_pairs(-nd, ids)

    return jax.lax.map(one, qs_r)


@partial(jax.jit, static_argnames=("kc", "metric", "recall_target"))
def knn_rank_int8(xs_q, arow, x2, valid, qs_r, kc: int,
                  metric: str = "euclidean", recall_target: float = 0.95):
    """Candidate-ranking kernel for stores too big for a bf16+f32 pair in
    HBM (e.g. 10M×768 ≈ 46 GB at 6 B/elem vs 16 GB on a v5e chip): the
    ranking store is per-row-scaled int8 (1 B/elem, 7.7 GB at 10M×768),
    the matmul runs int8×int8→int32 on the MXU, and the EXACT rescore of
    the returned candidates happens on the host from the f64/f32 source
    rows (idx/vector.py), so device memory never holds a full-precision
    copy.

    `xs_q` [N, D] int8 where row r ≈ x_r / arow[r] (cosine mode quantizes
    the pre-normalized rows); `arow` [N] f32 per-row dequant scale;
    `x2` [N] f32 row norms² (euclidean) — pass zeros otherwise;
    `qs_r` [R, B, D] f32 query chunks. Returns candidate ids [R, B, kc].
    Reference hot loop replaced: idx/trees/hnsw/layer.rs:184-223."""

    def one(qs):
        sq = 127.0 / jnp.maximum(jnp.abs(qs).max(axis=1), 1e-30)  # [B]
        q8 = jnp.round(qs * sq[:, None]).astype(jnp.int8)
        dots = jnp.einsum(
            "nd,bd->bn", xs_q, q8, preferred_element_type=jnp.int32
        )
        # dequantize: true dot ≈ dots * arow / sq
        approx = dots.astype(jnp.float32) * (arow[None, :] / sq[:, None])
        if metric == "euclidean":
            score = x2[None, :] - 2.0 * approx
        else:  # cosine (pre-normalized rows) / dot
            score = -approx
        score = jnp.where(valid[None, :], score, jnp.inf)
        _, cand = jax.lax.approx_max_k(
            -score, kc, recall_target=recall_target
        )
        return cand

    return jax.lax.map(one, qs_r)


@partial(jax.jit, static_argnames=("k", "metric", "block", "packed"))
def knn_search_blocked(xs, qs, k: int, metric: str = "euclidean",
                       p: float = 3.0, valid=None,
                       block: int = SCAN_BLOCK, packed: bool = False):
    """Blockwise scan for stores too large to materialize [B, N] at once:
    a loop over row blocks keeping a running top-k (HBM-bandwidth bound,
    peak memory [B, block]). Every block is a `dynamic_slice` of the
    store as it lies: nothing is padded or copied, so a dispatch reads
    the rows once. The last block is moved back to end at the last row
    and the rows it shares with the one before are masked out. Returns
    as `knn_search` does."""
    from surrealdb_tpu.ops.distance import distance_matrix

    n = xs.shape[0]
    b = qs.shape[0]
    block = max(min(block, n), 1)
    nblocks = max((n + block - 1) // block, 1)
    if valid is None:
        valid = jnp.ones((n,), dtype=bool)
    offs = jnp.arange(block, dtype=jnp.int32)
    kb = min(k, block)

    def step(i, carry):
        best_d, best_i = carry
        base = i * block
        start = jnp.minimum(base, n - block)
        blk = jax.lax.dynamic_slice_in_dim(xs, start, block, 0)
        rows = start + offs
        vmask = jax.lax.dynamic_slice_in_dim(valid, start, block, 0) \
            & (rows >= base)
        d = distance_matrix(blk, qs, metric, p)
        d = jnp.where(vmask[None, :], d, jnp.inf)
        cand_d, cand_i = jax.lax.top_k(-d, kb)
        merged_d = jnp.concatenate([best_d, -cand_d], axis=1)
        merged_i = jnp.concatenate([best_i, cand_i + start], axis=1)
        nd, sel = jax.lax.top_k(-merged_d, k)
        return -nd, jnp.take_along_axis(merged_i, sel, axis=1)

    init = (
        jnp.full((b, k), jnp.inf, dtype=jnp.float32),
        jnp.full((b, k), -1, dtype=jnp.int32),
    )
    fd, fi = jax.lax.fori_loop(0, nblocks, step, init)
    return pack_pairs(fd, fi) if packed else (fd, fi)


@partial(jax.jit, static_argnames=("k", "metric", "block_rows"))
def exact_scan(xs, qs, k: int, metric: str, p, valid, block_rows: int):
    """The exact store's program (device/vecstore.py), under one name
    whatever the store's size: every row scored in f32
    (`distance_matrix`, `Precision.HIGHEST`), exact `lax.top_k`, no
    candidate set. `knn_search` where the [B, N] scores fit
    (`block_rows`), `knn_search_blocked` above. [B, D] f32 queries in,
    the one `pack_pairs` array [B, 2k] out."""
    if xs.shape[0] > block_rows:
        return knn_search_blocked(xs, qs, k, metric, p, valid, packed=True)
    return knn_search(xs, qs, k, metric, p, valid, packed=True)
