"""Seeded traffic: synthetic topical corpora and queries drawn from
them. Pure numpy; imports nothing of the program.

The scheme follows the repo's synthetic BEIR-like corpus (topics with a
Zipf-weighted private vocabulary plus a shared common pool; queries made
of salient private words of a source doc), vectorized so that 32768
docs take well under a second, with two changes that keep every seed's
work the same:

* doc lengths come in batches: every consecutive ``batch`` docs (the
  program's encode batch) hold one FIXED multiset of lengths, drawn once
  from the configuration's length distribution with ``SIZE_SEED``, in
  the seed's order; a second topic replaces a quarter of a doc's private
  words instead of lengthening it. Token ids, topics and queries come
  from the seed.
* query lengths are a fixed multiset too, permuted by the seed.

With the same sizes in every encode batch of every seed, every program
shape (pooled vectors per batch, padded widths, shard sizes) is the same
on every seed, so the compile cache of the first run serves all later
ones.
"""
from __future__ import annotations

import numpy as np

SIZE_SEED = 20240924            # fixes the multisets of sizes
FIRST_WORD_ID = 24              # ids below are special or punctuation


def _zipf(n: int, a: float) -> np.ndarray:
    w = np.arange(1, n + 1, dtype=np.float64) ** (-a)
    return w / w.sum()


def length_template(n: int, mean: float, std: float, lo: int, hi: int
                    ) -> np.ndarray:
    """The fixed multiset of ``n`` doc lengths (sorted)."""
    rng = np.random.default_rng(SIZE_SEED)
    return np.sort(np.clip(np.rint(rng.normal(mean, std, n)), lo, hi)
                   ).astype(np.int64)


class Corpus:
    """Token-id docs of one traffic mix, made from ``seed``.

    Docs are made in blocks of ``block`` docs, each from (seed, block
    index): ``doc_tokens(ids)`` rebuilds any docs by index, so a
    reference can remake exactly the docs it samples, and a stream of
    any length needs no corpus in memory."""

    def __init__(self, t: dict, vocab_size: int, body: int, seed: int,
                 lengths: np.ndarray, block: int):
        self.t = t
        self.block = int(block)
        self.body = int(body)
        self.seed = int(seed)
        rng = np.random.default_rng([SIZE_SEED, self.seed])
        nw = vocab_size - FIRST_WORD_ID
        n_priv = int(t["private_vocab"]) * int(t["n_topics"])
        perm = rng.permutation(nw)[:n_priv + int(t["common_vocab"])]
        perm = perm + FIRST_WORD_ID
        self.common = perm[:int(t["common_vocab"])]
        self.topics = perm[int(t["common_vocab"]):].reshape(
            int(t["n_topics"]), int(t["private_vocab"]))
        self.zp = _zipf(int(t["private_vocab"]), float(t["zipf_a"]))
        self.zc = _zipf(int(t["common_vocab"]), float(t["zipf_a"]))
        self.lengths = np.minimum(lengths, self.body)

    @property
    def n_docs(self) -> int:
        return len(self.lengths)

    def block_tokens(self, b: int) -> np.ndarray:
        """[block, body] int32 tokens (0-padded) of docs
        ``[b * block, (b + 1) * block)``; a block's words depend only on
        (seed, b), so any doc can be rebuilt without the rest."""
        t = self.t
        lo = b * self.block
        lens = self.lengths[lo:lo + self.block]
        n, W = len(lens), self.body
        rng = np.random.default_rng([self.seed, 1, int(b)])
        nt = int(t["n_topics"])
        topic = rng.integers(nt, size=n)
        topic2 = np.where(rng.random(n) < float(t["secondary_topic_frac"]),
                          rng.integers(nt, size=n), topic)
        n_priv = lens - (lens * float(t["common_frac"])).astype(np.int64)
        pos = np.arange(W)[None, :]
        priv = self.topics[np.where(pos < n_priv[:, None] // 4,
                                    topic2[:, None], topic[:, None]),
                           rng.choice(len(self.zp), (n, W), p=self.zp)]
        common = self.common[rng.choice(len(self.zc), (n, W), p=self.zc)]
        words = np.where(pos < n_priv[:, None], priv, common)
        # shuffle each doc's first L words; slots past L stay padding
        keys = np.where(pos < lens[:, None], rng.random((n, W)), 2.0)
        words = np.take_along_axis(words, np.argsort(keys, axis=1), axis=1)
        return np.where(pos < lens[:, None], words, 0).astype(np.int32)

    def doc_tokens(self, ids) -> np.ndarray:
        """[len(ids), body] tokens of the docs ``ids``."""
        ids = np.asarray(ids, np.int64)
        out = np.zeros((len(ids), self.body), np.int32)
        blocks = {}
        for row, d in enumerate(ids):
            b = int(d) // self.block
            if b not in blocks:
                blocks[b] = self.block_tokens(b)
            out[row] = blocks[b][int(d) % self.block]
        return out

    def all_tokens(self) -> np.ndarray:
        nb = -(-self.n_docs // self.block)
        return np.concatenate([self.block_tokens(b) for b in range(nb)])

    def queries(self, n: int, qlen_lo: int, qlen_hi: int, width: int
                ) -> tuple:
        """([n, width] int32 queries, [n] source doc ids): each query is
        made of salient private words of its seeded source doc. Query
        lengths are a fixed multiset in [qlen_lo, qlen_hi), permuted by
        the seed."""
        rng = np.random.default_rng([self.seed, 2])
        qlens = np.random.default_rng(SIZE_SEED).integers(
            qlen_lo, qlen_hi, n)
        qlens = rng.permutation(qlens)
        src = rng.integers(0, self.n_docs, n)
        toks = self.doc_tokens(src)
        out = np.zeros((n, width), np.int32)
        priv = np.zeros(max(self.topics.max(), self.common.max()) + 1, bool)
        priv[self.topics.ravel()] = True
        for i in range(n):
            d = toks[i][toks[i] > 0]
            cand = np.unique(d[priv[d]])
            if len(cand) == 0:
                cand = np.unique(d)
            q = rng.choice(cand, min(int(qlens[i]), len(cand), width),
                           replace=False)
            out[i, :len(q)] = q
        return out, src


def corpus_for(cfg: dict, vocab_size: int, body: int, seed: int,
               n_docs: int, batch: int) -> Corpus:
    """The configuration's corpus for ``seed``: ``n_docs`` docs in which
    every consecutive ``batch`` docs hold the same fixed multiset of
    lengths (``length_template``), each batch in its own seeded order."""
    t = cfg["corpus"]
    lo, mean, std = int(t["doc_len_min"]), t["doc_len_mean"], t["doc_len_std"]
    rng = np.random.default_rng([SIZE_SEED, int(seed), 3])
    tmpl = length_template(batch, mean, std, lo, body)
    reps = -(-n_docs // batch)
    lens = np.concatenate([rng.permutation(tmpl) for _ in range(reps)])
    return Corpus(t, vocab_size, body, seed, lens[:n_docs], block=batch)
