"""The one token generator behind every traffic mix (`bench/traffic/*.json`
gives its rows, sequence length and ascent share): token rows drawn from
`--seed`, fed through the program's own pipeline.

`TokenSource` has the `batch(n_seqs, seq_len, stream)` surface that
`repro.data.TokenPipeline` takes as its `source`, so the pipeline's stream
indexing, ascent sub-batch and prefetch thread run as in training while every
token comes from here. Rows are uniform over the vocabulary, which is what
the program's own synthetic stream draws at this vocabulary size; labels are
the next token, with the last position masked (-1).
"""
from __future__ import annotations

import numpy as np


def rows(seed: int, vocab: int, n_seqs: int, seq_len: int,
         stream: int) -> np.ndarray:
    """(n_seqs, seq_len) int32 tokens of one stream."""
    rng = np.random.default_rng((seed, stream))
    return rng.integers(0, vocab, size=(n_seqs, seq_len), dtype=np.int32)


def labels_of(tokens: np.ndarray) -> np.ndarray:
    labels = np.roll(tokens, -1, axis=-1)
    labels[..., -1] = -1
    return labels


class TokenSource:
    def __init__(self, seed: int, vocab: int):
        self.seed = seed
        self.vocab = vocab

    def batch(self, n_seqs: int, seq_len: int, stream: int = 0) -> dict:
        import jax.numpy as jnp
        tokens = rows(self.seed, self.vocab, n_seqs, seq_len, stream)
        return {"tokens": jnp.asarray(tokens),
                "labels": jnp.asarray(labels_of(tokens))}


def unknown_rows(fed: list[np.ndarray], seed: int, vocab: int,
                 streams: int) -> int:
    """Rows of the fed token arrays that the generator did not produce in
    any of its first `streams` streams, plus rows fed more than once.

    0 means the program trained on exactly the generated rows, each once;
    a token altered on the way, or a row repeated, counts.
    """
    if not fed:
        return 0
    n_max = max(f.shape[0] for f in fed)
    seq = fed[0].shape[1]
    known = set()
    for s in range(streams):
        for r in rows(seed, vocab, n_max, seq, s):
            known.add(r.tobytes())
    # a stream drawn for fewer rows is a prefix of the same stream
    seen: set = set()
    bad = 0
    for f in fed:
        for r in f:
            b = r.tobytes()
            bad += b not in known or b in seen
            seen.add(b)
    return bad
