"""Deterministic, shard-aware synthetic data pipeline.

Real multi-pod training feeds each data-parallel shard a disjoint stream;
here the stream is synthetic but the *pipeline contract* is production-
shaped: batches are a pure function of (step, shard), so any worker can
reconstruct its stream after a restart (checkpoint stores only the step),
and elastic re-sharding just changes the (shard, num_shards) split.

Two generators:
  * ``MarkovLM`` — tokens from a fixed random sparse bigram chain:
    compressible structure a small LM can actually learn (loss drops well
    below log(vocab)), used by the quality benchmarks (paper Table 3
    analogue) and cheap enough for a full vocabulary.
  * ``frontend_features`` — Gaussian stand-ins for the VLM/audio stubs.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig

#: Successors per token of :class:`MarkovLM`.
FANOUT = 32


@dataclasses.dataclass
class MarkovLM:
    """Fixed random sparse bigram chain over ``vocab`` tokens.

    Each token has :data:`FANOUT` seeded successors with softmax weights
    over Gaussian logits scaled by ``temperature``, so the chain's tables
    and each sampled token cost O(vocab x FANOUT), not O(vocab^2): a
    full-vocabulary model (64,000 ids) gets its stream in megabytes.  At
    the default temperature the conditional entropy is about 2.2 nats at
    512 ids (at most log(FANOUT)), well below log(vocab): a small LM
    visibly learns it within tens of steps.
    """
    vocab: int
    seed: int = 0
    temperature: float = 2.0

    def __post_init__(self):
        rng = np.random.default_rng(self.seed)
        k = min(FANOUT, self.vocab)
        logits = rng.normal(size=(self.vocab, k)) * self.temperature
        w = np.exp(logits - logits.max(axis=1, keepdims=True))
        self._weights = w / w.sum(axis=1, keepdims=True)
        self._cum = np.cumsum(self._weights, axis=1)
        # a successor drawn twice just carries both weights
        self._succ = rng.integers(0, self.vocab, (self.vocab, k),
                                  dtype=np.int32)

    def transition_prob(self, prev: np.ndarray, nxt: np.ndarray
                        ) -> np.ndarray:
        """P(nxt | prev) of the chain, elementwise over equal shapes."""
        prev = np.asarray(prev)
        hit = self._succ[prev] == np.asarray(nxt)[..., None]
        return (self._weights[prev] * hit).sum(axis=-1)

    def sample(self, batch: int, seq_len: int, *, step: int, shard: int = 0
               ) -> np.ndarray:
        """(batch, seq_len+1) token ids, deterministic in (step, shard)."""
        rng = np.random.default_rng(
            np.random.SeedSequence([self.seed, step, shard]))
        out = np.empty((batch, seq_len + 1), np.int64)
        out[:, 0] = rng.integers(0, self.vocab, batch)
        u = rng.random((batch, seq_len))
        last = self._cum.shape[1] - 1
        for t in range(seq_len):
            prev = out[:, t]
            j = (self._cum[prev] < u[:, t:t + 1]).sum(axis=1)
            out[:, t + 1] = self._succ[prev, np.minimum(j, last)]
        return out


class Pipeline:
    """Batch source for an LM train loop."""

    def __init__(self, cfg: ModelConfig, batch: int, seq_len: int, *,
                 seed: int = 0, shard: int = 0, num_shards: int = 1):
        self.cfg = cfg
        self.batch = batch
        self.seq_len = seq_len
        self.shard = shard
        self.num_shards = num_shards
        self.lm = MarkovLM(cfg.vocab_size, seed=seed)
        self._feat_rng_seed = seed + 17

    def get_batch(self, step: int) -> Dict[str, jax.Array]:
        cfg = self.cfg
        S = self.seq_len
        if cfg.frontend:
            S_text = S - cfg.frontend_tokens
        else:
            S_text = S
        toks = self.lm.sample(self.batch, S_text, step=step, shard=self.shard)
        out = {
            "tokens": jnp.asarray(toks[:, :-1], jnp.int32),
            "labels": jnp.asarray(toks[:, 1:], jnp.int32),
        }
        rng = np.random.default_rng(
            np.random.SeedSequence([self._feat_rng_seed, step, self.shard]))
        dt = jnp.dtype(cfg.dtype)
        if cfg.enc_layers:
            out["frames"] = jnp.asarray(
                rng.normal(size=(self.batch, S, cfg.d_model)) * 0.1, dt)
        elif cfg.frontend:
            out["frontend"] = jnp.asarray(
                rng.normal(size=(self.batch, cfg.frontend_tokens,
                                 cfg.d_model)) * 0.1, dt)
        return out


def classification_task(n: int, dim: int, classes: int, *, seed: int = 0):
    """Gaussian-cluster classification set for the quality benchmarks."""
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(classes, dim)) * 2.0
    y = rng.integers(0, classes, n)
    x = centers[y] + rng.normal(size=(n, dim))
    return jnp.asarray(x, jnp.float32), jnp.asarray(y, jnp.int32)
