"""The one traffic generator: seeded documents packed into sequences.

A traffic file under ``traffic/`` holds its parameters (``zipf_s``,
``mean_doc_len``, ``follow_share``, ``pool``).  Token ids are drawn from a
unigram law proportional to ``rank ** -zipf_s`` (0 gives uniform ids), each
position follows a fixed random successor of the previous token with
probability ``follow_share``, and documents (BOS ... EOS, exponential
lengths) are shuffled and packed greedily into ``(batch, seq_len)`` blocks
with no padding.  Every seed gives the same shapes; only the ids differ.
"""

from __future__ import annotations

import numpy as np

BOS, EOS = 1, 2


def _documents(rng, vocab: int, zipf_s: float, mean_len: int,
               follow: float):
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    unigram = ranks ** -float(zipf_s)
    unigram /= unigram.sum()
    successor = rng.permutation(vocab)
    while True:
        n = max(4, int(rng.exponential(mean_len)))
        toks = rng.choice(vocab, size=n, p=unigram)
        keep = rng.random(n) < follow
        toks[1:] = np.where(keep[1:], successor[toks[:-1]], toks[1:])
        toks[0], toks[-1] = BOS, EOS
        yield toks.astype(np.int32)


def batches(params: dict, *, vocab: int, batch: int, seq_len: int,
            seed_seq: np.random.SeedSequence) -> list[dict]:
    """``params["pool"]`` distinct batches ``{"tokens", "labels"}``."""
    rng = np.random.default_rng(seed_seq)
    docs = _documents(rng, vocab, params["zipf_s"], params["mean_doc_len"],
                      params.get("follow_share", 0.5))
    need = batch * seq_len
    shuffle = 64
    stream = np.zeros((0,), np.int32)
    out = []
    for _ in range(params["pool"]):
        while stream.size < need:
            buf = [next(docs) for _ in range(shuffle)]
            rng.shuffle(buf)
            stream = np.concatenate([stream, *buf])
        block, stream = stream[:need], stream[need:]
        toks = block.reshape(batch, seq_len)
        out.append({"tokens": toks, "labels": toks.copy()})
    return out
