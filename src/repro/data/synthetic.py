"""Deterministic synthetic LM data pipeline.

Sequences follow a fixed random bigram chain over the vocabulary with
tunable noise, so a model that learns bigram statistics drives the loss
well below the unigram entropy — good enough to validate end-to-end
training dynamics without shipping a corpus.  Batches are a pure function
of (seed, step), so every data shard / restart is reproducible.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import trace


@dataclasses.dataclass
class SyntheticLM:
    vocab: int
    seq_len: int
    global_batch: int
    seed: int = 0
    noise: float = 0.1
    branch: int = 4          # successors per token in the bigram chain

    def __post_init__(self):
        rng = np.random.default_rng(self.seed)
        self.table = rng.integers(0, self.vocab,
                                  size=(self.vocab, self.branch),
                                  dtype=np.int64)

    def batch(self, step: int) -> dict:
        with trace.span(trace.DATA_BUILD):
            rng = np.random.default_rng((self.seed, step))
            B, T = self.global_batch, self.seq_len + 1
            toks = np.empty((B, T), np.int64)
            toks[:, 0] = rng.integers(0, self.vocab, size=B)
            branch = rng.integers(0, self.branch, size=(B, T))
            noise_mask = rng.random((B, T)) < self.noise
            noise_tok = rng.integers(0, self.vocab, size=(B, T))
            for t in range(1, T):
                nxt = self.table[toks[:, t - 1], branch[:, t]]
                toks[:, t] = np.where(noise_mask[:, t], noise_tok[:, t], nxt)
        with trace.span(trace.DATA_PUT):
            return dict(tokens=jnp.asarray(toks[:, :-1], jnp.int32),
                        labels=jnp.asarray(toks[:, 1:], jnp.int32))


def make_batch_specs(mesh, batch_axes=("data",)):
    return dict(tokens=NamedSharding(mesh, P(batch_axes, None)),
                labels=NamedSharding(mesh, P(batch_axes, None)))


def shard_batch(batch: dict, shardings: dict) -> dict:
    with trace.span(trace.DATA_PUT):
        return {k: jax.device_put(v, shardings[k]) if k in shardings else v
                for k, v in batch.items()}
