"""Runner-side CSR graph blocks: the JAX half of graph/csr.py.

The serving process ships rows/cols edge arrays once per cache epoch;
a multi-hop expansion arrives as a [B, n] batch of start-node masks
(the cross-query batcher stacks concurrent traversals) and leaves as
the reached-node masks — frontiers never materialize id values between
hops (jax.lax.scan over gather + scatter-or)."""

from __future__ import annotations

import numpy as np


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


class CsrStore:
    """Device-resident adjacency for ONE graph cache epoch."""

    def __init__(self, key: str, rows: np.ndarray, cols: np.ndarray,
                 n_nodes: int):
        self.key = key
        self.n_nodes = int(n_nodes)
        self.rows = rows
        self.cols = cols
        self.device = None

    def nbytes(self) -> int:
        return int(self.rows.nbytes + self.cols.nbytes)

    def device_nbytes(self) -> int:
        """Device-resident bytes once ensured (the two edge arrays
        move to the device as-is). Runner byte-budget ledger."""
        return self.nbytes()

    def _ensure(self):
        if self.device is None:
            import jax.numpy as jnp

            self.device = (jnp.asarray(self.rows), jnp.asarray(self.cols))
        return self.device

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
        bucket = 1
        while bucket < b:
            bucket *= 2
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
