"""Data pipeline: deterministic, host-sharded token streams with
prefetching and monitoring hooks.

Two sources:

* :class:`SyntheticSource` — deterministic tokens from (seed, step, host);
  zero I/O, used by smoke tests and dry-run-adjacent examples.
* :class:`MemmapSource` — a binary token corpus on disk, read via memmap
  with host-strided offsets (each host reads a disjoint stripe); this is
  the production-shaped path.

The :class:`Pipeline` wraps a source with a background prefetch thread and
reports fetch-wait time to the monitor (the paper's I/O data source —
input stalls are a classic cause of "low GFLOP/s" jobs).
"""

from __future__ import annotations

import queue
import threading
import time
from pathlib import Path
from typing import Dict, Iterator, Optional

import numpy as np

from repro.configs.base import ArchConfig
from repro.core.sources import PipelineStats
from repro.core.telemetry import Telemetry


class SyntheticSource:
    """Deterministic synthetic batches (tokens or stub embeddings)."""

    def __init__(self, cfg: ArchConfig, seq_len: int, batch: int,
                 host_id: int = 0, num_hosts: int = 1, seed: int = 0):
        assert batch % num_hosts == 0, (batch, num_hosts)
        self.cfg = cfg
        self.seq_len = seq_len
        self.local_batch = batch // num_hosts
        self.host_id = host_id
        self.seed = seed

    def get(self, step: int) -> Dict[str, np.ndarray]:
        rng = np.random.default_rng(
            (self.seed * 1_000_003 + step) * 97 + self.host_id)
        cfg, s, b = self.cfg, self.seq_len, self.local_batch
        out: Dict[str, np.ndarray] = {}
        if cfg.frontend == "audio_frames":
            out["embeds"] = rng.standard_normal(
                (b, s, cfg.d_model)).astype(np.float32) * 0.1
            labels = rng.integers(0, cfg.vocab_size, (b, s))
        else:
            toks = rng.integers(0, cfg.vocab_size, (b, s + 1))
            out["tokens"] = toks[:, :-1].astype(np.int32)
            labels = toks[:, 1:]
        out["labels"] = labels.astype(np.int32)
        out["loss_mask"] = np.ones((b, s), np.float32)
        if cfg.frontend == "image_patches":
            out["image_embeds"] = rng.standard_normal(
                (b, cfg.num_image_tokens, cfg.d_model)
            ).astype(np.float32) * 0.1
        return out


class MemmapSource:
    """Token stripes from a flat binary corpus (uint32 little-endian).

    Host h reads batch rows [h*local_b, (h+1)*local_b) of each step's
    window; windows advance by global_batch*seq tokens per step and wrap.
    """

    def __init__(self, corpus_path, cfg: ArchConfig, seq_len: int,
                 batch: int, host_id: int = 0, num_hosts: int = 1):
        assert batch % num_hosts == 0
        self.cfg = cfg
        self.seq_len = seq_len
        self.batch = batch
        self.local_batch = batch // num_hosts
        self.host_id = host_id
        self.data = np.memmap(corpus_path, dtype=np.uint32, mode="r")
        need = (seq_len + 1) * batch
        if len(self.data) < need:
            raise ValueError(f"corpus too small: {len(self.data)} < {need}")

    @staticmethod
    def write_synthetic_corpus(path, vocab_size: int, num_tokens: int,
                               seed: int = 0) -> Path:
        rng = np.random.default_rng(seed)
        arr = rng.integers(0, vocab_size, num_tokens, dtype=np.uint32)
        path = Path(path)
        path.parent.mkdir(parents=True, exist_ok=True)
        arr.tofile(path)
        return path

    def get(self, step: int) -> Dict[str, np.ndarray]:
        s, b = self.seq_len, self.local_batch
        row = s + 1
        step_span = self.batch * row
        usable = (len(self.data) // row) * row
        base = (step * step_span) % max(usable - step_span, row)
        start = base + self.host_id * b * row
        window = np.asarray(
            self.data[start:start + b * row]).reshape(b, row)
        toks = np.minimum(window, self.cfg.vocab_size - 1)
        out = {
            "tokens": toks[:, :-1].astype(np.int32),
            "labels": toks[:, 1:].astype(np.int32),
            "loss_mask": np.ones((b, s), np.float32),
        }
        if self.cfg.frontend == "image_patches":
            rng = np.random.default_rng(step)
            out["image_embeds"] = rng.standard_normal(
                (b, self.cfg.num_image_tokens, self.cfg.d_model)
            ).astype(np.float32) * 0.1
        return out


class Pipeline:
    """Background-prefetching wrapper with monitoring hooks.

    :meth:`next`'s wait for the queue is a ``repro.pipeline.wait`` span in
    the pipeline's ``telemetry``, written into a running profiler
    session's trace."""

    def __init__(self, source, stats: Optional[PipelineStats] = None,
                 prefetch: int = 2, start_step: int = 0):
        self.source = source
        self.stats = stats or PipelineStats()
        self.telemetry = Telemetry()
        self._q: queue.Queue = queue.Queue(maxsize=max(prefetch, 1))
        self._stop = threading.Event()
        self._step = start_step
        self._thread = threading.Thread(target=self._fill, daemon=True)
        self._thread.start()

    def _fill(self) -> None:
        step = self._step
        while not self._stop.is_set():
            batch = self.source.get(step)
            while not self._stop.is_set():
                try:
                    self._q.put((step, batch), timeout=0.1)
                    break
                except queue.Full:
                    continue
            step += 1

    def next(self) -> Dict[str, np.ndarray]:
        t0 = time.perf_counter()
        with self.telemetry.span("repro.pipeline.wait"):
            step, batch = self._q.get()
        wait = time.perf_counter() - t0
        tokens = int(batch.get("tokens", batch.get("embeds")).shape[0]
                     * self.source.seq_len)
        self.stats.on_batch(tokens, wait)
        return batch

    def __iter__(self) -> Iterator[Dict[str, np.ndarray]]:
        while True:
            yield self.next()

    def close(self) -> None:
        self._stop.set()
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=2)
