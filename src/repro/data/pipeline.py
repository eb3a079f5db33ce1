"""DataPipeline — background-prefetched, straggler-tolerant input pipeline.

BuffetFS-informed design choices:

* **Metadata off the hot path** — `warm_dirs()` caches every shard directory
  once; after that an epoch of N sample reads costs exactly N critical-path
  RPCs (the paper's headline property), not 2–3N.
* **Prefetch with deferred commit** — batch k+1 is fetched while step k
  computes (the BuffetFS "defer bookkeeping" insight applied to the device
  side: the training step never waits for I/O in steady state).
* **Hedged reads** — if a sample read exceeds `hedge_delay_s` (a straggling
  or dead BServer), the same sample is requested from its replica directory
  and the first response wins: tail-latency (straggler) mitigation.
"""
from __future__ import annotations

import concurrent.futures as cf
import queue
import threading
from dataclasses import dataclass, field
from typing import Dict, Iterator, Optional

import numpy as np

from .. import obs
from .dataset import BuffetDataset
from .sampler import ShardedSampler
from .tokens import pack_batch


@dataclass
class PipelineStats:
    batches: int = 0
    samples: int = 0
    hedged: int = 0
    hedge_wins: int = 0


class DataPipeline:
    def __init__(self, dataset: BuffetDataset, sampler: ShardedSampler, *,
                 seq_len: int, prefetch: int = 2, io_threads: int = 4,
                 hedge_delay_s: Optional[float] = None,
                 pad_id: int = 0) -> None:
        self.dataset = dataset
        self.sampler = sampler
        self.seq_len = seq_len
        self.pad_id = pad_id
        self.hedge_delay_s = hedge_delay_s
        self.stats = PipelineStats()
        self._pool = cf.ThreadPoolExecutor(max_workers=io_threads,
                                           thread_name_prefix="buffet-io")
        self._hedge_pool = cf.ThreadPoolExecutor(max_workers=io_threads,
                                                 thread_name_prefix="buffet-hedge")
        self._q: "queue.Queue[Optional[Dict[str, np.ndarray]]]" = queue.Queue(
            maxsize=prefetch)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._start_step = sampler.step
        self.consumed = 0  # batches handed to the consumer

    # --- sample read with hedging ---------------------------------------
    def _read_sample(self, idx: int) -> np.ndarray:
        if self.hedge_delay_s is None or not self.dataset.spec.replicated:
            return self.dataset.read_sample(idx)
        primary = self._hedge_pool.submit(self.dataset.read_sample, idx)
        try:
            return primary.result(timeout=self.hedge_delay_s)
        except cf.TimeoutError:
            # straggler: race the replica against the slow primary
            self.stats.hedged += 1
            secondary = self._hedge_pool.submit(
                self.dataset.read_sample, idx, replica=True)
            while True:
                done, pending = cf.wait({primary, secondary},
                                        return_when=cf.FIRST_COMPLETED)
                for f in done:
                    if f.exception() is None:
                        if f is secondary:
                            self.stats.hedge_wins += 1
                        return f.result()
                if not pending:  # both failed
                    raise primary.exception()
        except Exception:
            # primary failed fast (server down): read the replica directly
            self.stats.hedged += 1
            out = self.dataset.read_sample(idx, replica=True)
            self.stats.hedge_wins += 1
            return out

    def _build_batch(self, indices) -> Dict[str, np.ndarray]:
        samples = list(self._pool.map(self._read_sample, indices))
        with obs.span("data.pack"):
            tokens, mask = pack_batch(samples, self.seq_len + 1, self.pad_id)
        self.stats.batches += 1
        self.stats.samples += len(samples)
        return {
            "tokens": tokens[:, :-1],
            "labels": tokens[:, 1:].astype(np.int32),
            "loss_mask": mask[:, 1:],
        }

    # --- prefetch loop -----------------------------------------------------
    def _producer(self) -> None:
        it = iter(self.sampler)
        while not self._stop.is_set():
            try:
                indices = next(it)
                # the sampler's cursor is the step of the batch just drawn
                with obs.span("data.build_batch", step=self.sampler.step):
                    batch = self._build_batch(indices)
            except Exception as e:  # surface to the consumer, don't die mute
                batch = e
            while not self._stop.is_set():
                try:
                    self._q.put(batch, timeout=0.1)
                    break
                except queue.Full:
                    continue
            if isinstance(batch, Exception):
                return

    def start(self) -> "DataPipeline":
        self.dataset.warm_dirs()  # metadata RPCs happen HERE, once
        self._start_step = self.sampler.step
        self._thread = threading.Thread(target=self._producer,
                                        name="data-producer", daemon=True)
        self._thread.start()
        return self

    def __iter__(self) -> Iterator[Dict[str, np.ndarray]]:
        if self._thread is None:
            self.start()
        while True:
            item = self._q.get()
            if item is None:
                return
            if isinstance(item, Exception):
                raise item
            self.consumed += 1
            yield item

    def state_dict(self) -> dict:
        """Sampler state just after the batches the consumer has taken.

        The producer runs up to `prefetch + 1` batches ahead, so the
        sampler's own cursor is past where a resumed run must start."""
        return {**self.sampler.state_dict(),
                "step": self._start_step + self.consumed}

    def stop(self) -> None:
        self._stop.set()
        while True:  # unblock the producer if it is waiting on a full queue
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        try:
            self._q.put_nowait(None)
        except queue.Full:
            pass
        if self._thread is not None:
            self._thread.join(timeout=2.0)
        self._pool.shutdown(wait=False)
        self._hedge_pool.shutdown(wait=False)
