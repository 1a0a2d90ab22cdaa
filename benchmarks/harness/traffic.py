"""The one general generator of load. Every number comes from the mix's
data file (``traffic/<mix>.json``) and from ``--seed``; a new mix of a kind
that is here is a new data file and no code.

Kinds:

- ``train``: a job. A pool of token sequences with bigram structure (every
  token has ``branching`` likely successors, from a table fixed by
  ``table_seed``), so that the loss has something to learn; batches walk
  the pool in a seeded order, every row of a batch another sequence.
- ``serve``: requests. ``size_set`` (prompt, output) lengths are drawn once
  from ``size_seed``, so that every run seed offers the same set of sizes;
  the requests walk the set round and round, every walk in an order of its
  own drawn from the run seed, which also fills in the prompt tokens
  (uniform over the vocabulary). Under one order a seed the same prompts
  arrived together walk after walk, and that order, not the program,
  decided ``gpt2-medium.serve-closed``'s tails (PERF.md section 6, PR 39).
  Outputs are fixed by ``max_new_tokens`` (no EOS), decoding is greedy.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Iterator, List

import numpy as np


def _bigram_pool(n_seqs: int, width: int, vocab: int, branching: int,
                 table_seed: int, seed: int) -> np.ndarray:
    """int32 [n_seqs, width]; all sequences advance together, one position
    a numpy step."""
    successors = np.random.default_rng(table_seed).integers(
        0, vocab, size=(vocab, branching), dtype=np.int32)
    rng = np.random.default_rng(seed)
    out = np.empty((n_seqs, width), np.int32)
    out[:, 0] = rng.integers(0, vocab, size=n_seqs)
    choices = rng.integers(0, branching, size=(n_seqs, width))
    for t in range(1, width):
        out[:, t] = successors[out[:, t - 1], choices[:, t]]
    return out


class TrainBatches:
    """Iterable of host batches int32 [global_batch, seq_len + 1]. The walk
    over the pool is fixed by the seed; ``batch(i)`` is the i-th batch the
    iterator yields, for the reference to follow."""

    def __init__(self, mix: Dict[str, Any], vocab: int, global_batch: int,
                 seed: int) -> None:
        stream = mix["stream"]
        if stream["type"] != "bigram":
            raise ValueError(f"unknown stream type {stream['type']!r}")
        self.global_batch = int(global_batch)
        self.pool = _bigram_pool(
            int(mix["pool_sequences"]), int(mix["seq_len"]) + 1, vocab,
            int(stream["branching"]), int(stream["table_seed"]), seed)
        self.order = np.random.default_rng(seed + 1).permutation(
            len(self.pool))

    def batch(self, i: int) -> np.ndarray:
        n = len(self.pool)
        rows = self.order[(i * self.global_batch + np.arange(
            self.global_batch)) % n]
        return self.pool[rows]

    def __iter__(self) -> Iterator[np.ndarray]:
        i = 0
        while True:
            yield self.batch(i)
            i += 1


@dataclasses.dataclass(frozen=True)
class ServeRequest:
    index: int
    prompt: List[int]
    max_new_tokens: int


def _lengths(spec: Dict[str, Any], n: int, rng: np.random.Generator
             ) -> np.ndarray:
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    raw = rng.lognormal(np.log(spec["median"]), spec["sigma"], size=n)
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


def serve_requests(mix: Dict[str, Any], vocab: int, seed: int
                   ) -> List[ServeRequest]:
    """The run's requests in the order the clients take them."""
    n, k = int(mix["n_requests"]), int(mix["size_set"])
    sizes = np.random.default_rng(int(mix["size_seed"]))
    prompt_len = _lengths(mix["prompt_len"], k, sizes)
    output_len = _lengths(mix["output_len"], k, sizes)
    rng = np.random.default_rng(seed)
    # the orders first: a longer list then walks the same orders further
    size = np.concatenate([rng.permutation(k) for _ in range(-(-n // k))])
    tokens = rng.integers(0, vocab, size=(n, int(prompt_len.max())),
                          dtype=np.int32)
    return [ServeRequest(i, tokens[i, :prompt_len[size[i]]].tolist(),
                         int(output_len[size[i]]))
            for i in range(n)]
