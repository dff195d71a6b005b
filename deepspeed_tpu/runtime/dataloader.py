"""Data loading.

Reference: ``deepspeed/runtime/dataloader.py`` (DeepSpeedDataLoader wrapping a
DistributedSampler, RepeatingLoader). Under SPMD one process feeds the global
batch; sharding happens at device_put, so the "distributed sampler" is just
batch slicing per host in the multi-host case (each host yields its slice of
the global batch; jax.make_array_from_process_local_data assembles it).
"""

import collections
import math
from typing import Any, Callable, Iterable, Iterator, Optional

import numpy as np

from deepspeed_tpu.telemetry.tracing import span


class DataLoader:
    """Minimal batching loader over an indexable dataset of dict rows (or a
    callable index -> row)."""

    def __init__(self, dataset, batch_size: int, shuffle: bool = False,
                 seed: int = 0, drop_last: bool = True, collate_fn=None,
                 sampler=None):
        self.dataset = dataset
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.seed = seed
        self.drop_last = drop_last
        self.collate_fn = collate_fn or _default_collate
        if sampler is not None and shuffle:
            raise ValueError("pass shuffle to the sampler, not the loader, "
                             "when a sampler is given")
        self.sampler = sampler  # e.g. data_pipeline.DistributedSampler
        self.epoch = 0
        self._pos = 0          # batches yielded this epoch (ckpt position)
        self._resume_pos = 0   # batches to skip on the next __iter__

    def __len__(self):
        total = (len(self.sampler) if self.sampler is not None
                 else len(self.dataset))
        n = total // self.batch_size
        if not self.drop_last and total % self.batch_size:
            n += 1
        return n

    def set_epoch(self, epoch: int):
        self.epoch = epoch
        self._pos = 0

    # -- checkpointable position (robustness: elastic resume must neither
    # replay nor skip data). The order within an epoch is a pure function
    # of (seed, epoch), so (epoch, pos, seed) fully names the position.
    def state_dict(self) -> dict:
        return {"epoch": self.epoch, "pos": self._pos, "seed": self.seed}

    def load_state_dict(self, sd: dict) -> None:
        self.seed = int(sd.get("seed", self.seed))
        self.set_epoch(int(sd.get("epoch", 0)))
        # fast-forward happens lazily at the next __iter__: the shuffle
        # order is regenerated from (seed, epoch) and `pos` batches are
        # skipped, so the next yielded batch is exactly the first one the
        # saved run had not consumed
        self._resume_pos = int(sd.get("pos", 0))
        self._pos = self._resume_pos

    def __iter__(self) -> Iterator:
        if self.sampler is not None:
            if hasattr(self.sampler, "set_epoch"):
                self.sampler.set_epoch(self.epoch)
            order = np.fromiter(iter(self.sampler), dtype=np.int64)
            n = len(order)
        else:
            n = len(self.dataset)
            order = np.arange(n)
            if self.shuffle:
                rng = np.random.default_rng(self.seed + self.epoch)
                rng.shuffle(order)
        skip, self._resume_pos = self._resume_pos, 0
        self._pos = skip
        starts = range(0, n - (self.batch_size - 1 if self.drop_last else 0),
                       self.batch_size)
        for bi, start in enumerate(starts):
            if bi < skip:
                continue
            idx = order[start:start + self.batch_size]
            rows = [self.dataset[int(i)] for i in idx]
            self._pos = bi + 1
            yield self.collate_fn(rows)


class PrefetchLoader:
    """Double-buffered device prefetch for the async step pipeline.

    Wraps any host-batch iterable and starts the sharding-aware
    ``device_put`` of batch N+1 while the consumer runs step N: JAX dispatch
    is asynchronous, so ``put_fn`` returns as soon as the H2D transfer is
    *queued* and the copy overlaps the in-flight step instead of sitting on
    the dispatch critical path (the reference hides the same latency behind
    a side CUDA stream).

    ``put_fn`` is typically ``engine._device_batch`` — idempotent: a leaf
    already placed with the target sharding passes through untouched, so the
    engine's curriculum/LTD/PLD batch rewrites compose (a rewritten leaf is
    simply re-placed at consume time).

    ``depth=2`` is classic double buffering; higher depths only help when
    batch production (collate) is burstier than one step. Batch ORDER is the
    wrapped loader's order — prefetch reorders nothing, including across
    epoch boundaries (``set_epoch``/``epoch`` proxy through).

    Each device_put top-up is a ``ds:train.prefetch`` span (visible to any
    profiler session); ``tracer`` (a telemetry ``StepTracer``) also records
    it as a ``prefetch`` span in the step trace timeline.
    """

    def __init__(self, loader, put_fn: Callable[[Any], Any], depth: int = 2,
                 tracer=None):
        if put_fn is None:
            raise ValueError("PrefetchLoader needs a device placement fn "
                             "(engine._device_batch)")
        self.loader = loader
        self.put_fn = put_fn
        self.depth = max(1, int(depth))
        self.tracer = tracer

    def _put(self, batch):
        with (self.tracer.span("prefetch", cat="data")
              if self.tracer is not None else span("ds:train.prefetch")):
            return self.put_fn(batch)

    def __len__(self):
        return len(self.loader)

    @property
    def epoch(self):
        return getattr(self.loader, "epoch", 0)

    def set_epoch(self, epoch: int):
        if hasattr(self.loader, "set_epoch"):
            self.loader.set_epoch(epoch)

    def __iter__(self) -> Iterator:
        it = iter(self.loader)
        buf = collections.deque()
        try:
            while len(buf) < self.depth:
                buf.append(self._put(next(it)))
        except StopIteration:
            pass
        while buf:
            out = buf.popleft()
            # top up BEFORE yielding: the put of batch N+depth is queued
            # while the consumer still holds (and then steps on) batch N
            try:
                buf.append(self._put(next(it)))
            except StopIteration:
                pass
            yield out


class RepeatingLoader:
    """Infinite cycling wrapper (reference: dataloader.py RepeatingLoader)."""

    def __init__(self, loader):
        self.loader = loader
        self._it = iter(loader)

    def __iter__(self):
        return self

    def __next__(self):
        try:
            return next(self._it)
        except StopIteration:
            if hasattr(self.loader, "set_epoch"):
                self.loader.set_epoch(getattr(self.loader, "epoch", 0) + 1)
            self._it = iter(self.loader)
            return next(self._it)

    # position checkpointing proxies (engine.attach_dataloader works with
    # either the bare DataLoader or this wrapper)
    def state_dict(self) -> dict:
        return self.loader.state_dict()

    def load_state_dict(self, sd: dict) -> None:
        self.loader.load_state_dict(sd)
        # drop the live iterator: it was positioned for the OLD state, and
        # DataLoader's lazy fast-forward applies at the next iter()
        self._it = iter(self.loader)


def _default_collate(rows):
    if isinstance(rows[0], dict):
        return {k: np.stack([r[k] for r in rows]) for k in rows[0]}
    if isinstance(rows[0], (tuple, list)):
        return tuple(np.stack([r[i] for r in rows]) for i in range(len(rows[0])))
    return np.stack(rows)


def random_token_batches(batch_size: int, seq_len: int, vocab_size: int,
                         num_batches: int, seed: int = 0):
    """Synthetic LM data (reference: tests/unit/simple_model.py
    random_dataloader equivalent)."""
    rng = np.random.default_rng(seed)
    for _ in range(num_batches):
        ids = rng.integers(0, vocab_size, size=(batch_size, seq_len), dtype=np.int32)
        yield {"input_ids": ids}
