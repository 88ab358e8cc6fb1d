"""Plain reference for full-text search: BM25 over token-id arrays in f64
numpy, and reciprocal-rank fusion.

It shares nothing with the served path (no index keys, no kernel, no
batcher, no cache) and states the semantics the served path is held to
(`tests/test_ft_device.py`):

- a document's length is its token count; N is the number of documents
  with at least one token, avg_len their mean length;
- a term's idf is `ln((N - df + 0.5) / (df + 0.5))` clamped at 0, so a term
  in more than half the documents still has to match and adds nothing;
- a matching term adds `idf * (k1 + 1) * tf' / (tf' + k1 * ((1 - b) + b *
  dl / avg_len))` with `tf' = 1 + ln tf`, k1 and b as f32 values (the index
  definition's parameters are held in f32);
- AND: documents that hold every distinct query term; OR: any of them;
- the ranking is by score descending, equal scores in document order;
- `rrf(lists, limit, k)`: each id scores the sum of 1 / (k + rank + 1) over
  the lists it appears in (rank from 0), ids ordered by that score
  descending, equal scores in order of first appearance.
"""

from __future__ import annotations

import numpy as np


class Corpus:
    """Documents as arrays of term ids; postings built once, in numpy."""

    def __init__(self, docs: list, k1: float = 1.2, b: float = 0.75):
        self.k1 = float(np.float32(k1))
        self.b = float(np.float32(b))
        lens = np.asarray([len(d) for d in docs], np.int64)
        self.dl = lens
        toks = np.concatenate([np.asarray(d, np.int64) for d in docs]) \
            if docs else np.zeros(0, np.int64)
        doc = np.repeat(np.arange(len(docs)), lens)
        pair, tf = np.unique(np.stack([toks, doc], 1), axis=0,
                             return_counts=True) if len(toks) \
            else (np.zeros((0, 2), np.int64), np.zeros(0, np.int64))
        self.post_term, self.post_doc = pair[:, 0], pair[:, 1]
        self.post_tf = tf
        self.n = max(int((lens > 0).sum()), 1)
        self.avg_len = float(lens.sum()) / self.n

    def postings(self, term: int):
        lo, hi = np.searchsorted(self.post_term, [term, term + 1])
        return self.post_doc[lo:hi], self.post_tf[lo:hi]

    def search(self, terms: list, boolean: str = "AND"):
        """(doc numbers, f64 scores) of every match, best first."""
        terms = list(dict.fromkeys(terms))
        if not terms:
            return np.zeros(0, np.int64), np.zeros(0)
        parts = [self.postings(t) for t in terms]
        if boolean == "AND":
            docs = parts[0][0]
            for d, _tf in parts[1:]:
                docs = np.intersect1d(docs, d)
        else:
            docs = np.unique(np.concatenate([d for d, _tf in parts]))
        k1, b = self.k1, self.b
        norm = (1 - b) + (b / max(self.avg_len, 1e-9)) * self.dl[docs]
        score = np.zeros(len(docs))
        for d, tf in parts:
            df = len(d)
            if df == 0:
                continue
            idf = max(np.log((self.n - df + 0.5) / (df + 0.5)), 0.0)
            at = np.searchsorted(d, docs)
            hit = (at < len(d)) & (d[np.minimum(at, len(d) - 1)] == docs)
            tfd = np.where(hit, tf[np.minimum(at, len(d) - 1)], 0)
            tfp = 1.0 + np.log(np.maximum(tfd, 1))
            score = score + np.where(
                hit & (idf > 0), idf * (k1 + 1) * tfp / (tfp + k1 * norm),
                0.0)
        order = np.lexsort((docs, -score))
        return docs[order], score[order]


def rrf(lists: list, limit: int, k: float = 60):
    """[(id, fused score)] of the first `limit` ids (module docstring)."""
    scores: dict = {}
    for lst in lists:
        for rank, i in enumerate(lst):
            scores[i] = scores.get(i, 0.0) + 1.0 / (k + rank + 1)
    order = sorted(scores, key=lambda i: -scores[i])
    return [(i, scores[i]) for i in order[:limit]]
