"""Runner-side full-text blocks: one FULLTEXT index's postings as CSR on the
chip, and the batched BM25 top-n over them (`idx/ftdevice.py` is the
serving half).

A store holds, for the index version it was shipped at:

- `indptr` [T + 1] int32 over term numbers (the serving side's numbering);
- `docs` [P] int32 doc numbers, ascending within each term's slice;
- `tf` [P] int32 term frequencies, beside `docs`;
- `dl` [N] int32 BM25 document lengths by doc number;
- `rows` [T] int32, each term's row of `dense` (-1 for none), and `dense`
  [R, N] the tf of every doc (0: absent) for the R terms in more docs than
  the last listed rung holds (`rungs_for`), uint8 where every tf fits.

A rider is one query: its live term count and up to T distinct term numbers,
the rarest first, and each term's BM25 weight `idf * (k1 + 1)` as f32 bits.
Under AND semantics only a doc in the rarest term's slice can match, so
that slice is the rider's candidate list: it rides a power-of-two CAPACITY
(a rung of `rungs_for`, chosen by the serving side from the term's df),
every other term is tested by a fixed-step binary search in its own sorted
slice (all of them at once, one step a loop iteration), and a candidate
that holds every term is scored in f32: a rider reads
O(df_min * T * log df) postings. A rider whose rarest term is in more
docs than the last listed rung holds has every term in `dense`: it rides
the DENSE rung (capacity: every doc) and each doc is scored from the
terms' dense rows, read whole, in place of a candidate list and its
binary searches, whose gathers would cost that rider tens of times what a
light one costs. Then an exact
`lax.top_k` of `k` (equal scores: the lower doc number first) and ONE
packed int32 array back a rider: the AND-match count, and for each of the
`k` places the doc number (-1 past the matches), the f32 score bits, the
doc length and the tf of every term slot.

A rider whose rarest term holds more postings than its rung allows (the
serving side chose the rung from a df the store does not have) is not run:
its row says so (`-1 - df`) and it goes round again on the rung that holds
it (`idx/ftdevice.py`), counted in `kernelstats.FT["overflows"]`.
"""

from __future__ import annotations

from functools import lru_cache

import numpy as np

from surrealdb_tpu.device.csrstore import pad_len, pow2_at_least

# capacity rungs of the candidate list: 64, 512, 4,096, ... (x8 a rung),
# the last cut to the power of two that holds the largest slice. A
# 6-word query's rarest word is mostly in a few dozen documents: every
# slot, live or padding, pays the binary searches
FT_MIN_CAP = 64
FT_RUNG_STEP = 8
# candidate slots one program call may hold (riders x capacity): a rung
# at or above 65,536 takes one rider a call, 64 takes up to 1,024
FT_SLOTS = 1 << 16
# rider buckets a ship pre-compiles on every rung (1, 2, 4, ... up to
# this, or to what one call of the rung holds)
FT_PREWARM_RIDERS = 32
# the first listed rung past which riders may take the dense rung: terms
# in more docs than it get a dense tf row (a 56-word passage's commonest
# ~400 words of 2**20 at 400,000 passages), if the rows hold at most
# FT_DENSE_CELLS tfs, else the next rung is listed and tried
FT_DENSE_FROM = 4096
FT_DENSE_CELLS = 1 << 28


def prewarm_shapes(rungs: tuple) -> list:
    """[(capacity, riders)] of the programs a ship compiles ahead."""
    out = []
    for cap in rungs:
        b = 1
        while b <= min(FT_PREWARM_RIDERS, riders_per_call(cap)):
            out.append((cap, b))
            b *= 2
    return out


def ladder(df: np.ndarray, n_docs: int) -> tuple:
    """(rungs, dense) of a store of `n_docs` docs whose terms are in `df`
    docs each: the capacity ladder from FT_MIN_CAP up to the first rung
    that holds the largest slice, that one no larger than the power of
    two that does; or, from FT_DENSE_FROM on, up to the first rung whose
    larger terms' dense rows fit FT_DENSE_CELLS, then the dense rung,
    `dense` = `pad_len(n_docs)` (every doc); `dense` 0 where there is
    none."""
    max_df = int(df.max()) if len(df) else 0
    out = [FT_MIN_CAP]
    for _ in range(10):      # 64 * 8**10 passes any int32 df
        if out[-1] >= max_df:
            break
        if out[-1] >= FT_DENSE_FROM and int(
                (df > out[-1]).sum()) * pad_len(n_docs) <= FT_DENSE_CELLS:
            out.append(pad_len(n_docs))
            return tuple(out), out[-1]
        out.append(min(out[-1] * FT_RUNG_STEP, pow2_at_least(max_df)))
    return tuple(out), 0


def rungs_for(df: np.ndarray, n_docs: int) -> tuple:
    return ladder(df, n_docs)[0]


def rung_for(df: int, rungs: tuple) -> int:
    """The smallest capacity of `rungs` that holds `df` candidates."""
    for cap in rungs:
        if cap >= df:
            return cap
    return rungs[-1]


def riders_per_call(cap: int) -> int:
    return max(FT_SLOTS // cap, 1)


def term_bucket(n_terms: int) -> int:
    """Term slots a rider of `n_terms` distinct terms rides (8 or more)."""
    return max(pow2_at_least(max(n_terms, 1)), 8)


def row_width(t: int, k: int) -> int:
    """int32 words of one rider's reply: count, then per place the doc,
    the score bits, dl and `t` tfs."""
    return 1 + k * (3 + t)


def _and(matched, found, used):
    """A candidate still matches if it holds every other term (or the
    slot is padding): AND over the rider's terms. `found` and `used` are
    [B, T - 1, C]."""
    return matched & (found | ~used).all(axis=1)


def _unpack(packed):
    """(live term counts, term numbers, f32 weights) of [B, 1 + 2T]."""
    import jax
    import jax.numpy as jnp

    t_ = (packed.shape[1] - 1) // 2
    return packed[:, 0], packed[:, 1:1 + t_], \
        jax.lax.bitcast_convert_type(packed[:, 1 + t_:], jnp.float32)


def _term_score(tfs, wj, norm, k1):
    import jax.numpy as jnp

    tfp = 1.0 + jnp.log(jnp.maximum(tfs, 1).astype(jnp.float32))
    return wj * tfp / (tfp + k1 * norm)


def _reply(count, score, cand, tfs, dl, k):
    """The packed rows: `score` [B, C] (-inf: no match) of the docs `cand`
    [B, C], `tfs` [B, T, C] their tf of every term slot."""
    import jax
    import jax.numpy as jnp

    b_, t_ = tfs.shape[0], tfs.shape[1]
    vals, idx = jax.lax.top_k(score, k)
    hit = vals > -jnp.inf
    ids = jnp.where(hit, jnp.take_along_axis(cand, idx, 1), -1)
    tf_k = jnp.take_along_axis(tfs, jnp.broadcast_to(
        idx[:, None, :], (b_, t_, k)), 2)
    tf_k = jnp.where(hit[:, None], tf_k, 0)
    return jnp.concatenate([
        count[:, None], ids, jax.lax.bitcast_convert_type(vals, jnp.int32),
        jnp.where(hit, dl[jnp.maximum(ids, 0)], 0),
        tf_k.reshape(b_, t_ * k)], axis=1)


def _bm25_impl(indptr, docs, tf, dl, rows, dense, packed, consts, cap, k,
               steps, on_dense):
    """`packed` [B, 1 + 2T] int32: live terms, T term numbers (rarest
    first; slots past the live count are ignored), T weights as f32 bits.
    `consts` f32 [3]: k1, 1 - b, b / avg_len. Returns [B, 1 + k(3 + T)]
    int32 (module docstring). On a listed rung the other terms are
    searched together, one step of every binary search a loop iteration
    of the program; on the dense rung (`on_dense`) `_bm25_dense`."""
    import jax
    import jax.numpy as jnp

    if on_dense:
        return _bm25_dense(dl, rows, dense, packed, consts, k)
    n_t, terms, w = _unpack(packed)
    b_, t_ = terms.shape
    k1, one_minus_b, b_avg = consts[0], consts[1], consts[2]
    last = docs.shape[0] - 1
    lo = indptr[terms]
    hi = indptr[terms + 1]
    df0 = jnp.where(n_t > 0, hi[:, 0] - lo[:, 0], 0)
    slot = jnp.arange(cap, dtype=jnp.int32)[None, :]
    live = slot < df0[:, None]
    pos0 = jnp.where(live, lo[:, :1] + slot, 0)
    cand = jnp.where(live, docs[pos0], 0)
    norm = one_minus_b + b_avg * dl[cand].astype(jnp.float32)
    tf0 = jnp.where(live, tf[pos0], 0)
    # the other terms' slices, searched for every candidate at once:
    # [B, T - 1, C], the first doc >= the candidate in each
    lo_r, hi_r = lo[:, 1:, None], hi[:, 1:, None]
    target = cand[:, None, :]
    shape = (b_, t_ - 1, cap)

    def step(_s, c):
        at, n = c
        half = n // 2
        go = (n > 0) & (docs[jnp.minimum(at + half, last)] < target)
        return jnp.where(go, at + half + 1, at), \
            jnp.where(go, n - half - 1, half)

    at, _n = jax.lax.fori_loop(0, steps, step, (
        jnp.broadcast_to(lo_r, shape), jnp.broadcast_to(hi_r - lo_r, shape)))
    found = (at < hi_r) & (docs[jnp.minimum(at, last)] == target)
    used = (jnp.arange(1, t_)[None, :] < n_t[:, None])[:, :, None]
    tf_o = jnp.where(found & used, tf[jnp.minimum(at, last)], 0)
    score = _term_score(tf0, w[:, :1], norm, k1) + jnp.sum(jnp.where(
        found & used, _term_score(tf_o, w[:, 1:, None], norm[:, None], k1),
        0.0), axis=1)
    matched = _and(live, found, used)
    return _reply(jnp.sum(matched, axis=1, dtype=jnp.int32),
                  jnp.where(matched, score, -jnp.inf), cand,
                  jnp.concatenate([tf0[:, None], tf_o], axis=1), dl, k)


def _bm25_dense(dl, rows, dense, packed, consts, k):
    """The dense rung: every live term of every rider has a row of
    `dense`, so each doc is a candidate, scored from the rows read whole
    ([B, T, N], contiguous) with the same arithmetic, in the same order
    (the rarest term, then the sum of the others)."""
    import jax.numpy as jnp

    n_t, terms, w = _unpack(packed)
    b_, t_ = terms.shape
    k1, one_minus_b, b_avg = consts[0], consts[1], consts[2]
    n = dense.shape[1]
    tfs = dense[jnp.maximum(rows[terms], 0)].astype(jnp.int32)
    norm = one_minus_b + b_avg * dl[:n].astype(jnp.float32)
    live = (tfs[:, 0] > 0) & (n_t > 0)[:, None]
    tf0 = jnp.where(live, tfs[:, 0], 0)
    found = tfs[:, 1:] > 0
    used = (jnp.arange(1, t_)[None, :] < n_t[:, None])[:, :, None]
    tf_o = jnp.where(found & used, tfs[:, 1:], 0)
    score = _term_score(tf0, w[:, :1], norm[None, :], k1) + jnp.sum(
        jnp.where(found & used, _term_score(
            tf_o, w[:, 1:, None], norm[None, None, :], k1), 0.0), axis=1)
    matched = _and(live, found, used)
    cand = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[None, :], (b_, n))
    return _reply(jnp.sum(matched, axis=1, dtype=jnp.int32),
                  jnp.where(matched, score, -jnp.inf), cand,
                  jnp.concatenate([tf0[:, None], tf_o], axis=1), dl, k)


@lru_cache(maxsize=None)
def _bm25_jit():
    import jax

    return jax.jit(_bm25_impl, static_argnums=(8, 9, 10, 11))


class FtStore:
    """One index version's postings on the device (module docstring).
    The host keeps `indptr` too: a rider's rarest slice is checked
    against its rung before anything is launched."""

    def __init__(self, key: str, indptr: np.ndarray, docs: np.ndarray,
                 tf: np.ndarray, dl: np.ndarray):
        self.key = key
        self.indptr = np.ascontiguousarray(indptr, np.int32)
        self.n_postings = int(len(docs))
        self.n_docs = int(len(dl))
        df = np.diff(self.indptr)
        self.max_df = int(df.max()) if len(df) else 0
        self.steps = max(self.max_df.bit_length(), 1)
        self.rungs, self.dense_cap = ladder(df, self.n_docs)
        p = pad_len(self.n_postings)
        self._host = (
            self._padded(self.indptr, pad_len(len(self.indptr)),
                         self.n_postings),
            self._padded(docs, p, 0), self._padded(tf, p, 0),
            self._padded(dl, pad_len(self.n_docs), 0)) + self._dense_rows(
                df, docs, tf)
        self.device = None

    def _dense_rows(self, df, docs, tf) -> tuple:
        """(`rows`, `dense`) of the terms in more docs than the last listed
        rung holds; placeholders where the ladder has no dense rung."""
        if not self.dense_cap:
            return np.zeros(1, np.int32), np.zeros((1, 1), np.uint8)
        big = np.nonzero(df > self.rungs[-2])[0]
        rows = np.full(pad_len(len(df)), -1, np.int32)
        rows[big] = np.arange(len(big), dtype=np.int32)
        ip = self.indptr
        top = max((int(tf[ip[t]:ip[t + 1]].max()) for t in big), default=0)
        dense = np.zeros((len(big), self.dense_cap),
                         np.uint8 if top <= 255 else np.int32)
        for r, t in enumerate(big.tolist()):
            dense[r, docs[ip[t]:ip[t + 1]]] = tf[ip[t]:ip[t + 1]]
        return rows, dense

    @staticmethod
    def _padded(a, n: int, fill: int) -> np.ndarray:
        out = np.full(n, fill, np.int32)
        out[:len(a)] = a
        return out

    def nbytes(self) -> int:
        return int(sum(a.nbytes for a in self._host))

    def device_nbytes(self) -> int:
        return self.nbytes()

    def _ensure(self):
        if self.device is None:
            import jax.numpy as jnp

            self.device = tuple(jnp.asarray(a) for a in self._host)
        return self.device

    def bm25(self, packed: np.ndarray, consts, cap: int, k: int):
        """[R, 1 + 2T] int32 riders on rung `cap` -> [R, 1 + k(3 + T)]
        int32 rows; a rider past its rung reads `-1 - df` and nothing
        else. Calls of at most `riders_per_call(cap)` riders, each padded
        to a power of two. Counts into `kernelstats.FT`."""
        from surrealdb_tpu.device.kernelstats import FT

        r, t = packed.shape[0], (packed.shape[1] - 1) // 2
        out = np.zeros((r, row_width(t, k)), np.int32)
        ip = self.indptr
        first = packed[:, 1]
        df0 = np.where(packed[:, 0] > 0, ip[first + 1] - ip[first], 0)
        over = df0 > cap
        out[over, 0] = -1 - df0[over]
        FT["overflows"] += int(over.sum())
        run = np.nonzero(~over)[0]
        step = riders_per_call(cap)
        for s in range(0, len(run), step):
            rows = run[s:s + step]
            out[rows] = self._launch(packed[rows], consts, cap, k)
            FT["dispatches"] += 1
        live = packed[run]
        FT["riders"] += len(run)
        if cap == self.dense_cap:
            # each live term's dense row and the doc lengths, unpadded
            FT["dense_bytes"] += int(self.n_docs * (
                live[:, 0].astype(np.int64) * self._host[5].itemsize
                + 4).sum())
            return out
        # what the answers needed, unpadded: each candidate's doc, tf and
        # dl once, and every other live term's probes of a lower bound
        cands = df0[run]
        FT["candidates"] += int(cands.sum())
        probes = np.zeros(len(run), np.int64)
        for j in range(1, t):
            tj = live[:, 1 + j]
            dfj = np.where(live[:, 0] > j, ip[tj + 1] - ip[tj], 0)
            probes += np.where(live[:, 0] > j,
                               np.ceil(np.log2(dfj + 1.0)).astype(np.int64)
                               + 1, 0)
        FT["postings_read"] += int((cands * (1 + probes)).sum())
        return out

    def _launch(self, batch: np.ndarray, consts, cap: int, k: int):
        """One program call: the riders padded to a power of two (a
        padded rider has no live term and so no candidate)."""
        from surrealdb_tpu.device.kernelstats import note_shape, phase

        n = len(batch)
        bucket = pow2_at_least(n)
        if bucket != n:
            batch = np.concatenate([batch, np.zeros(
                (bucket - n, batch.shape[1]), np.int32)])
        note_shape("ft_bm25", (self._host[1].shape[0],
                               self._host[3].shape[0], self._host[5].shape,
                               batch.shape, cap, k, self.steps))
        with phase("device"):
            got = np.asarray(_bm25_jit()(*self._ensure(), batch,
                                         np.asarray(consts, np.float32),
                                         cap, k, self.steps,
                                         cap == self.dense_cap))
        return got[:n]

    def warm(self, t: int, k: int, cap: int, riders: int):
        """Compiles the program of `riders` riders on rung `cap` with `t`
        term slots and `k` places."""
        self._launch(np.zeros((riders, 1 + 2 * t), np.int32),
                     (1.2, 0.25, 0.01), cap, min(k, cap))
