"""Deterministic, resume-safe data streams: every batch is a pure function
of (seed, step) — a restart replays exactly the unapplied batches."""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp


def pair_batch(step: int, *, batch: int, seq: int, vocab: int,
               n_rel_terms: int = 4, seed: int = 0) -> dict:
    """(query, positive doc) pairs for sparse-encoder distillation: docs
    share salient terms with their query; teacher score = overlap count."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, step, 1]))
    salient = rng.integers(1, vocab, size=(batch, n_rel_terms))
    q = np.concatenate([salient, rng.integers(1, vocab,
                                              (batch, seq - n_rel_terms))], 1)
    d_pos = np.concatenate([salient, rng.integers(1, vocab,
                                                  (batch, seq - n_rel_terms))],
                           1)
    d_neg = rng.integers(1, vocab, size=(batch, seq))
    return {"query": jnp.asarray(q.astype(np.int32)),
            "doc_pos": jnp.asarray(d_pos.astype(np.int32)),
            "doc_neg": jnp.asarray(d_neg.astype(np.int32))}
