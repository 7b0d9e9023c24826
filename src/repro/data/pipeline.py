"""Sharded batching + host prefetch.

``GlobalBatchLoader`` materialises each device's shard of the global batch
locally via ``jax.make_array_from_callback`` — no host ever holds the full
global batch, which is what makes 1000-node data loading feasible. A
background thread keeps ``prefetch`` batches in flight.
"""
from __future__ import annotations

import queue
import threading
from typing import Any, Callable, Dict, Iterator, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.data.synthetic import batch_for_step


class GlobalBatchLoader:
    """Yields globally-sharded batches; each shard generated independently."""

    def __init__(self, cfg, mesh: Optional[Mesh], batch: int, seq: int, *,
                 seed: int = 0, start_step: int = 0):
        self.cfg, self.mesh = cfg, mesh
        self.batch, self.seq, self.seed = batch, seq, seed
        self.step = start_step

    def _sharding(self, leaf_ndim: int) -> Optional[NamedSharding]:
        if self.mesh is None:
            return None
        axes = [a for a in ("pod", "data") if a in self.mesh.axis_names]
        spec = P(tuple(axes), *([None] * (leaf_ndim - 1)))
        return NamedSharding(self.mesh, spec)

    def batch_at(self, step: int) -> Dict[str, Any]:
        host = batch_for_step(self.cfg, step, self.batch, self.seq,
                              seed=self.seed)
        if self.mesh is None:
            return {k: jax.numpy.asarray(v) for k, v in host.items()}
        out = {}
        for k, v in host.items():
            sh = self._sharding(v.ndim)

            def cb(idx, _v=v):
                return _v[idx]

            out[k] = jax.make_array_from_callback(v.shape, sh, cb)
        return out

    def __iter__(self) -> Iterator[Dict[str, Any]]:
        while True:
            yield self.batch_at(self.step)
            self.step += 1


class Prefetcher:
    """Runs a loader iterator on a background thread with a bounded queue."""

    def __init__(self, it: Iterator, prefetch: int = 2):
        self.q: "queue.Queue" = queue.Queue(maxsize=prefetch)
        self._stop = threading.Event()

        def worker():
            for item in it:
                while not self._stop.is_set():
                    try:
                        self.q.put(item, timeout=0.05)
                        break
                    except queue.Full:
                        continue
                if self._stop.is_set():
                    return

        self.t = threading.Thread(target=worker, daemon=True)
        self.t.start()

    def __iter__(self):
        return self

    def __next__(self):
        return self.q.get()

    def close(self):
        """Stop the worker and wait for it: a worker still producing
        batches while the interpreter shuts down crashes the process."""
        self._stop.set()
        try:
            while True:
                self.q.get_nowait()
        except queue.Empty:
            pass
        self.t.join(timeout=30)
