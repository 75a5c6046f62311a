"""Host-sharded deterministic data pipeline with background prefetch.

Multi-host posture: every host computes the SAME epoch permutation from the
(seed, epoch) pair and takes its ``process_index``-strided slice, so no
host-to-host coordination is needed and restarts are deterministic given
(seed, step) — the trainer checkpoints the step counter and the pipeline
fast-forwards. Prefetch is a small thread + queue to overlap host batch
assembly with device compute.
"""
from __future__ import annotations

import queue
import threading
from typing import Callable, Dict, Iterator, Optional

import jax
import numpy as np


class DataPipeline:
    def __init__(self, n_examples: int, batch_size: int,
                 make_batch: Callable[[np.ndarray], Dict],
                 seed: int = 0, shard_index: Optional[int] = None,
                 shard_count: Optional[int] = None, prefetch: int = 2,
                 drop_remainder: bool = True):
        """make_batch: maps example-id array [B] -> batch dict of arrays."""
        self.n = n_examples
        self.bs = batch_size
        self.make_batch = make_batch
        self.seed = seed
        self.shard_index = (shard_index if shard_index is not None
                            else jax.process_index())
        self.shard_count = (shard_count if shard_count is not None
                            else jax.process_count())
        self.prefetch = prefetch
        self.drop_remainder = drop_remainder

    def _epoch_ids(self, epoch: int) -> np.ndarray:
        rng = np.random.default_rng((self.seed, epoch))
        perm = rng.permutation(self.n)
        return perm[self.shard_index::self.shard_count]

    def batches(self, start_step: int = 0) -> Iterator[Dict]:
        """Infinite batch iterator, fast-forwarded to ``start_step``."""
        q: queue.Queue = queue.Queue(maxsize=self.prefetch)
        stop = threading.Event()

        def produce():
            step = 0
            epoch = 0
            while not stop.is_set():
                ids = self._epoch_ids(epoch)
                nb = len(ids) // self.bs
                for b in range(nb):
                    if step >= start_step:
                        batch = self.make_batch(
                            ids[b * self.bs:(b + 1) * self.bs])
                        while not stop.is_set():
                            try:
                                q.put((step, batch), timeout=0.5)
                                break
                            except queue.Full:
                                continue
                    step += 1
                epoch += 1

        t = threading.Thread(target=produce, daemon=True)
        t.start()
        try:
            while True:
                step, batch = q.get()
                yield batch
        finally:
            stop.set()
