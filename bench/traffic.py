"""Host-side training traffic: a sparse Markov chain over the full vocabulary.

A numpy copy of the chain in ``repro.data.synthetic.MarkovLM``: every token
has ``branching`` successors with Dirichlet-distributed probabilities, so
the text has learnable structure. It runs on the host, never on the chip,
and is fed to the trainer through the program's ``DataPipeline``.

Everything follows from ``--seed``: the chain itself, and every row. The
rows of ``pool_steps`` steps are walked at once, vectorized over all of
them, when the traffic is made (set-up); step ``s`` then takes the pool's
batch ``s mod pool_steps``, so the input pipeline's thread does no work
of its own inside the timed window. Row streams are disjoint per Pier
group (group ``g`` draws from its own ``SeedSequence([seed, 1 + g])``),
and every row of the pool differs. A traffic file
(``bench/traffic/<name>.json``) gives the sizes.
"""

from __future__ import annotations

import numpy as np


class MarkovTraffic:
    """Batches ``{"tokens", "labels"}`` of shape (global_batch, seq_len).

    Rows ``[g·b, (g+1)·b)`` with ``b = global_batch / groups`` are group
    ``g``'s: the pipeline shards dim 0 over the data axes in that order.
    """

    def __init__(self, *, vocab: int, seq_len: int, global_batch: int,
                 groups: int, seed: int, branching: int = 8,
                 concentration: float = 0.5, pool_steps: int = 512):
        if seed < 0:
            raise ValueError(f"seed must be a whole number, got {seed}")
        if global_batch % groups:
            raise ValueError(f"global batch {global_batch} does not split "
                             f"over {groups} groups")
        self.vocab, self.seq_len = vocab, seq_len
        self.global_batch, self.groups = global_batch, groups
        self.rows_per_group = global_batch // groups
        self.seed, self.pool_steps = seed, pool_steps
        rng = np.random.default_rng([seed, 0])
        self.succ = rng.integers(0, vocab, size=(vocab, branching),
                                 dtype=np.int32)
        cum = np.cumsum(rng.dirichlet(np.full(branching, concentration),
                                      size=vocab), axis=1)
        cum[:, -1] = 1.0
        self.cum = cum
        self.pool = self._walk_pool()

    def _walk_pool(self) -> np.ndarray:
        """(pool_steps, global_batch, seq_len + 1) token walks."""
        P, G, b, T = (self.pool_steps, self.groups, self.rows_per_group,
                      self.seq_len)
        firsts, draws = [], []
        for g in range(G):
            rng = np.random.default_rng([self.seed, 1 + g])
            firsts.append(rng.integers(0, self.vocab, size=(P, b)))
            draws.append(rng.random((T, P, b)))
        tok = np.stack(firsts, axis=1).reshape(-1).astype(np.int32)
        u = np.stack(draws, axis=2).reshape(T, -1)
        last = self.succ.shape[1] - 1
        out = np.empty((T + 1, tok.size), np.int32)
        out[0] = tok
        for t in range(T):
            idx = (self.cum[tok] < u[t][:, None]).sum(axis=1)
            tok = self.succ[tok, np.minimum(idx, last)]
            out[t + 1] = tok
        return np.ascontiguousarray(out.T).reshape(P, G * b, T + 1)

    def walks(self, step: int) -> np.ndarray:
        """(global_batch, seq_len + 1) token walks of ``step``."""
        return self.pool[step % self.pool_steps]

    def batch(self, step: int) -> dict:
        w = self.walks(step)
        return {"tokens": np.ascontiguousarray(w[:, :-1]),
                "labels": np.ascontiguousarray(w[:, 1:])}
