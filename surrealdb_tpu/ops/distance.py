"""Batched distance kernels (replaces the reference's per-element scalar
distances, idx/trees/vector.rs:208-450, with MXU-shaped batch ops).

All kernels take `xs: [N, D]` and `qs: [B, D]` and return `[B, N]` distances.
Dot-product-expressible metrics (euclidean, cosine, dot) ride the MXU via
einsum; the rest (manhattan/chebyshev/minkowski/hamming) are VPU elementwise
reductions over a broadcast difference — still batched and fused by XLA.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

# metric ids kept stable for jit static args; the names (and
# normalize_metric) live in the jax-free ops/metrics.py so query-path
# code can import them without touching this kernel module
from surrealdb_tpu.ops.metrics import (  # noqa: F401 (re-export)
    CHEBYSHEV,
    COSINE,
    DOT,
    EUCLIDEAN,
    HAMMING,
    JACCARD,
    MANHATTAN,
    MINKOWSKI,
    PEARSON,
    normalize_metric,
)


# these are the EXACT kernels (non-ranking paths: brute scans, the mesh
# exact store): their f32 matmuls run at full f32 on the MXU, not the
# TPU's default single bf16 pass. The bf16/int8 RANKING matmuls live in
# ops/topk.py and stay fast on purpose.
_EXACT = jax.lax.Precision.HIGHEST


@partial(jax.jit, static_argnames=("metric",))
def distance_matrix(xs, qs, metric: str = EUCLIDEAN, p: float = 3.0):
    """[B, N] distances between each query row and every stored vector."""
    xs = xs.astype(jnp.float32)
    qs = qs.astype(jnp.float32)
    if metric == EUCLIDEAN:
        # |x-q|^2 = |x|^2 - 2 x.q + |q|^2  (one MXU matmul)
        x2 = jnp.sum(xs * xs, axis=-1)[None, :]
        q2 = jnp.sum(qs * qs, axis=-1)[:, None]
        xq = jnp.einsum("nd,bd->bn", xs, qs, precision=_EXACT)
        d2 = jnp.maximum(x2 + q2 - 2.0 * xq, 0.0)
        return jnp.sqrt(d2)
    if metric == COSINE:
        xn = xs / jnp.maximum(jnp.linalg.norm(xs, axis=-1, keepdims=True), 1e-30)
        qn = qs / jnp.maximum(jnp.linalg.norm(qs, axis=-1, keepdims=True), 1e-30)
        return 1.0 - jnp.einsum("nd,bd->bn", xn, qn, precision=_EXACT)
    if metric == DOT:
        return -jnp.einsum("nd,bd->bn", xs, qs, precision=_EXACT)
    if metric == MANHATTAN:
        return jnp.sum(jnp.abs(qs[:, None, :] - xs[None, :, :]), axis=-1)
    if metric == CHEBYSHEV:
        return jnp.max(jnp.abs(qs[:, None, :] - xs[None, :, :]), axis=-1)
    if metric == HAMMING:
        return jnp.sum(qs[:, None, :] != xs[None, :, :], axis=-1).astype(
            jnp.float32
        )
    if metric == MINKOWSKI:
        d = jnp.abs(qs[:, None, :] - xs[None, :, :])
        return jnp.power(jnp.sum(jnp.power(d, p), axis=-1), 1.0 / p)
    if metric == PEARSON:
        xc = xs - jnp.mean(xs, axis=-1, keepdims=True)
        qc = qs - jnp.mean(qs, axis=-1, keepdims=True)
        xn = xc / jnp.maximum(jnp.linalg.norm(xc, axis=-1, keepdims=True), 1e-30)
        qn = qc / jnp.maximum(jnp.linalg.norm(qc, axis=-1, keepdims=True), 1e-30)
        return 1.0 - jnp.einsum("nd,bd->bn", xn, qn, precision=_EXACT)
    if metric == JACCARD:
        # continuous jaccard distance: 1 - sum(min)/sum(max)
        mn = jnp.sum(jnp.minimum(qs[:, None, :], xs[None, :, :]), axis=-1)
        mx = jnp.sum(jnp.maximum(qs[:, None, :], xs[None, :, :]), axis=-1)
        return 1.0 - mn / jnp.maximum(mx, 1e-30)
    raise ValueError(f"unknown metric {metric!r}")


