"""Sharded input pipeline for the mesh trainer.

Produces global device arrays laid out over the mesh's data axes, with the
host batches made ahead by a background thread. Each Pier group consumes a
*disjoint* slice of the stream (the group's data-parallel shard), matching
the paper's Megatron data loader semantics.
"""

from __future__ import annotations

import queue
import threading
from typing import Callable, Dict, Iterator, Optional

import jax
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.config import ModelConfig, TrainConfig
from repro.data.synthetic import MarkovLM


class DataPipeline:
    """Iterator of sharded training batches.

    A producer thread calls ``make_batch`` and stages up to ``prefetch``
    host (numpy) batches in a queue; ``__next__`` takes the next one and
    puts it on the mesh (``jax.device_put``) on the consumer's thread.
    Under a running profiler the three parts show as host spans:
    ``pipeline.make_batch`` on the producer's thread,
    ``pipeline.queue_wait`` and ``pipeline.device_put`` on the consumer's.

    Args:
      mesh: the (refined) device mesh.
      batch_axes: mesh axis name(s) sharding dim 0 of every array.
      make_batch: fn(step) -> dict of host numpy arrays (global shape).
      prefetch: number of host batches to stage ahead.
    """

    def __init__(
        self,
        mesh: Mesh,
        batch_axes,
        make_batch: Callable[[int], Dict[str, np.ndarray]],
        *,
        prefetch: int = 2,
    ):
        self.mesh = mesh
        self.batch_axes = batch_axes
        self.make_batch = make_batch
        self._q: queue.Queue = queue.Queue(maxsize=max(prefetch, 1))
        self._step = 0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._producer, daemon=True)
        self._thread.start()

    def _shard(self, batch: Dict[str, np.ndarray]):
        out = {}
        for k, v in batch.items():
            full = P(self.batch_axes, *([None] * (v.ndim - 1)))
            out[k] = jax.device_put(v, NamedSharding(self.mesh, full))
        return out

    def _producer(self):
        step, batch = 0, None
        while not self._stop.is_set():
            if batch is None:
                with TraceAnnotation("pipeline.make_batch", step=step):
                    batch = self.make_batch(step)
            try:
                self._q.put(batch, timeout=1.0)
                step, batch = step + 1, None
            except queue.Full:
                continue

    def __iter__(self) -> Iterator[Dict[str, jax.Array]]:
        return self

    def __next__(self) -> Dict[str, jax.Array]:
        with TraceAnnotation("pipeline.queue_wait", step=self._step):
            batch = self._q.get()
        with TraceAnnotation("pipeline.device_put", step=self._step):
            out = self._shard(batch)
        self._step += 1
        return out

    def close(self):
        """Stop the producer and wait for it: a thread still inside jax
        when the interpreter exits aborts the process."""
        self._stop.set()
        self._thread.join()


def synthetic_pipeline(
    mesh: Mesh,
    batch_axes,
    mc: ModelConfig,
    tc: TrainConfig,
    *,
    seq_len: Optional[int] = None,
    global_batch: Optional[int] = None,
) -> DataPipeline:
    """Markov-LM pipeline producing {"tokens", "labels"} batches."""
    lm = MarkovLM(min(mc.vocab_size, 2048), seed=tc.seed)
    S = seq_len or tc.seq_len
    B = global_batch or tc.global_batch_size

    def make(step: int) -> Dict[str, np.ndarray]:
        key = jax.random.fold_in(jax.random.PRNGKey(tc.seed), step)
        toks = np.asarray(lm.sample(key, B, S))
        return {"tokens": toks[:, :-1].astype(np.int32),
                "labels": toks[:, 1:].astype(np.int32)}

    return DataPipeline(mesh, batch_axes, make)
