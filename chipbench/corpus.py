"""The training cell's corpus, made from the seed: shards of documents
with geometric lengths and a Zipf-like unigram distribution, and the
packed rows each shard must become (next-token labels, and a loss mask
that is 0 on padding and on the position that predicts across a
document's end).

The document lengths are one geometric draw (``doc_lengths_seed``), the
same set in every shard and for every seed, in an order drawn from the
seed: every shard then packs into as many rows with as much padding, so
the work of a step does not depend on the seed or on which shard landed
first.
"""
from __future__ import annotations

import collections
import hashlib
from typing import Dict, List

import numpy as np

PAD, EOD = 0, 1


class Unigram:
    """Zipf-like unigram over ids 2 .. vocab_size - 1 (0 = pad, 1 = end of
    document): the id of rank r has probability proportional to 1 / r."""

    def __init__(self, vocab_size: int):
        ranks = np.arange(2, vocab_size)
        p = 1.0 / ranks
        self.ids = ranks.astype(np.int32)
        self.cdf = np.cumsum(p / p.sum())

    def draw(self, rng: np.random.Generator, n: int) -> np.ndarray:
        u = rng.random(n)
        return self.ids[np.minimum(np.searchsorted(self.cdf, u),
                                   len(self.ids) - 1)]


class Corpus:
    def __init__(self, seed: int, traffic: Dict, vocab_size: int):
        self.seed = seed
        self.lengths = np.maximum(
            traffic["min_doc_len"],
            np.random.default_rng(traffic["doc_lengths_seed"]).geometric(
                1.0 / traffic["mean_doc_len"], traffic["docs_per_shard"]))
        self.seq_len = traffic["seq_len"]
        self.unigram = Unigram(vocab_size)

    def docs(self, shard: int) -> List[np.ndarray]:
        rng = np.random.default_rng([self.seed, shard])
        lens = rng.permutation(self.lengths)
        toks = self.unigram.draw(rng, int(lens.sum()))
        return np.split(toks, np.cumsum(lens)[:-1])

    def rows(self, shard: int) -> Dict[str, np.ndarray]:
        """Greedy packing of the shard's documents, each followed by the
        end-of-document id, into rows of ``seq_len + 1``."""
        docs = self.docs(shard)
        S = self.seq_len
        stream = np.concatenate([np.append(d, EOD) for d in docs])
        total = len(stream)
        n = max(1, (total + S) // (S + 1))
        flat = np.full(n * (S + 1), PAD, np.int32)
        keep = min(total, n * (S + 1))
        flat[:keep] = stream[:keep]
        valid = np.zeros(n * (S + 1), np.float32)
        valid[:keep] = 1.0
        ends = np.cumsum([len(d) + 1 for d in docs]) - 1
        eod = np.zeros(n * (S + 1), bool)
        eod[ends[ends < n * (S + 1)]] = True
        r, v, e = (a.reshape(n, S + 1) for a in (flat, valid, eod))
        return {"tokens": r[:, :-1], "labels": r[:, 1:],
                "loss_mask": v[:, 1:] * (1 - e[:, :-1]).astype(np.float32)}


def row_digests(batch: Dict[str, np.ndarray]) -> List[str]:
    out = []
    for i in range(batch["tokens"].shape[0]):
        h = hashlib.blake2b(digest_size=16)
        for k in ("tokens", "labels", "loss_mask"):
            h.update(np.ascontiguousarray(batch[k][i]).tobytes())
        out.append(h.hexdigest())
    return out


def rows_not_staged(consumed: collections.Counter,
                    corpus: Corpus, shards) -> int:
    """Consumed rows that are no packed row of the given shards, or that
    came more often than the shards hold them."""
    have: collections.Counter = collections.Counter()
    for s in shards:
        have.update(row_digests(corpus.rows(s)))
    return sum(max(0, n - have[d]) for d, n in consumed.items())
