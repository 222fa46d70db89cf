"""Batching pipeline: private per-client iterators + the public pool.

Each client owns its input pipeline; the indices of every batch are drawn
on the host, deterministic given seeds. The rows come from whichever copy
of the dataset the iterator was given: numpy arrays (a batch is numpy,
gathered on the host and uploaded at the jit boundary) or a `DeviceData`
(a batch is gathered on the device and only its indices are uploaded).
"""
from __future__ import annotations

import functools
import os
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Iterator, Mapping, Optional, Tuple, Union

import jax
import numpy as np

# Stride between per-client private-batch rng streams. Every algorithm
# (MHD runtime, FedMD, FedAvg, supervised baselines) must derive client
# iterator seeds through `client_stream_seed` so that cross-algorithm
# comparisons train on *identical* private sample orders — the paper's
# tables are comparative, and a different shuffle is a confound.
PRIVATE_STREAM_STRIDE = 13


def client_stream_seed(seed: int, client_id: int) -> int:
    """Seed of client ``client_id``'s private `BatchIterator` stream."""
    return seed + PRIVATE_STREAM_STRIDE * client_id


# The largest share of the device memory left free once the clients'
# parameters and optimizer state are placed that the dataset may take;
# the rest is for the updates' own peak. On a v5e the Mamba2-370m fleet of
# four peaks at 86% of that free memory, so at most 14% of it could hold
# data there; the ResNet-34 fleet of eight needs 8% of it for its 1.2 GB
# (PERF.md §6).
RESIDENT_SHARE = 0.1

# dtype of the row indices a device gather uploads
INDEX_DTYPE = np.int32


def dataset_nbytes(arrays: Mapping[str, np.ndarray]) -> int:
    """Bytes the arrays take on the device (JAX's dtypes: float64 data is
    held as float32)."""
    return sum(v.size * jax.dtypes.canonicalize_dtype(v.dtype).itemsize
               for v in arrays.values())


def device_memory_stats() -> Dict[str, int]:
    """The first device's allocator statistics; empty on a backend that
    keeps none (the CPU)."""
    return jax.devices()[0].memory_stats() or {}


def device_memory_free() -> Optional[int]:
    """Bytes the first device has free now; None where the backend reports
    no limit (the CPU)."""
    stats = device_memory_stats()
    if "bytes_limit" not in stats:
        return None
    return stats["bytes_limit"] - stats.get("bytes_in_use", 0)


def fits_on_device(nbytes: int) -> bool:
    """Whether a dataset of ``nbytes`` may live on the first device: at
    most `RESIDENT_SHARE` of the memory it has free now, any size where it
    reports no limit."""
    free = device_memory_free()
    return free is None or nbytes <= RESIDENT_SHARE * free


@functools.partial(jax.jit, static_argnums=2)
def _take_rows(rows, sel, shapes):
    def take(v):
        # one dynamic slice per selected row: XLA's gather of whole rows
        # (jnp.take) compiles on a TPU to a copy of the whole operand
        return jax.vmap(lambda i: jax.lax.dynamic_index_in_dim(
            v, i, keepdims=False))(sel)
    return {k: take(rows[k]).reshape(sel.shape + shape)
            for k, shape in shapes}


class DeviceData:
    """One copy of a dataset on the device, each array held as rows of
    ``(n, size of one sample)``: a sample is then contiguous, and a batch
    is whole rows copied on the device. (The TPU's own layout of an image
    array ``(n, h, w, c)`` puts ``n`` innermost.) The rows are exact
    copies; dtypes are JAX's, so int64 labels are held as int32."""

    def __init__(self, rows: Dict[str, jax.Array],
                 shapes: Tuple[Tuple[str, Tuple[int, ...]], ...]):
        self.rows = rows
        self.shapes = shapes

    @classmethod
    def put(cls, arrays: Mapping[str, np.ndarray]) -> "DeviceData":
        """Upload ``arrays`` with one ``jax.device_put``."""
        rows = jax.device_put({k: v.reshape(len(v), -1) if v.ndim > 1
                               else v for k, v in arrays.items()})
        return cls(rows, tuple((k, v.shape[1:]) for k, v in arrays.items()))

    def without(self, key: str) -> "DeviceData":
        """The same buffers less one array."""
        return DeviceData({k: v for k, v in self.rows.items() if k != key},
                          tuple(s for s in self.shapes if s[0] != key))

    def check(self, indices: np.ndarray) -> None:
        """A device read clamps an index out of range; refuse one here."""
        n = len(next(iter(self.rows.values())))
        if indices.size and not (0 <= indices.min() and indices.max() < n):
            raise IndexError(f"indices outside the {n} rows on the device")

    def take(self, sel: np.ndarray) -> Dict[str, jax.Array]:
        """Rows ``sel`` of every array, in their own shapes."""
        return _take_rows(self.rows, np.asarray(sel, INDEX_DTYPE),
                          self.shapes)


# A host batch of at least this many bytes is gathered by several threads.
# Its rows land in freshly allocated pages, and on a TPU VM's host the page
# faults of one thread cost several times the copy itself (PERF.md §6).
PARALLEL_GATHER_BYTES = 4 << 20
_GATHER_THREADS = min(8, os.cpu_count() or 1)


@functools.cache
def _gather_pool() -> ThreadPoolExecutor:
    return ThreadPoolExecutor(_GATHER_THREADS,
                              thread_name_prefix="batch-gather")


def host_take(v: np.ndarray, sel: np.ndarray) -> np.ndarray:
    """``v[sel]`` along the first axis; a large batch is split into blocks
    of rows copied by `_GATHER_THREADS` threads (numpy's copy releases the
    GIL). The same rows, indexing and errors as ``v[sel]``."""
    sel = np.asarray(sel)
    if sel.ndim != 1 or len(sel) < 2 or \
            len(sel) * v[:1].nbytes < PARALLEL_GATHER_BYTES:
        return v[sel]
    n = len(v)
    if not (-n <= sel.min() and sel.max() < n):
        raise IndexError(f"index out of bounds for {n} rows")
    out = np.empty((len(sel),) + v.shape[1:], v.dtype)

    def copy(rows: np.ndarray) -> None:
        for j in rows:
            out[j] = v[sel[j]]

    blocks = np.array_split(np.arange(len(sel)),
                            min(_GATHER_THREADS, len(sel)))
    list(_gather_pool().map(copy, blocks))
    return out


Arrays = Union[Dict[str, np.ndarray], DeviceData]


def _take(arrays: Arrays, sel: np.ndarray):
    if isinstance(arrays, DeviceData):
        return arrays.take(sel)
    return {k: host_take(v, sel) for k, v in arrays.items()}


class BatchIterator:
    """Infinite shuffled minibatch iterator over index-selected arrays:
    numpy batches from numpy arrays, device batches from a `DeviceData`
    (the same rows either way)."""

    def __init__(
        self,
        arrays: Arrays,
        indices: np.ndarray,
        batch_size: int,
        seed: int = 0,
        drop_remainder: bool = True,
    ):
        if indices.shape[0] == 0:
            raise ValueError("BatchIterator got an empty index set")
        self.arrays = arrays
        self.indices = np.asarray(indices)
        self.resident = isinstance(arrays, DeviceData)
        if self.resident:
            arrays.check(self.indices)
        self.batch_size = batch_size
        self.rng = np.random.default_rng(seed)
        self._order = self.rng.permutation(self.indices.shape[0])
        self._pos = 0

    def next(self) -> Dict[str, np.ndarray]:
        n = self.indices.shape[0]
        take = []
        need = self.batch_size
        while need > 0:
            if self._pos >= n:
                self._order = self.rng.permutation(n)
                self._pos = 0
            grab = min(need, n - self._pos)
            take.append(self._order[self._pos : self._pos + grab])
            self._pos += grab
            need -= grab
        sel = self.indices[np.concatenate(take)]
        return _take(self.arrays, sel)

    def __iter__(self) -> Iterator[Dict[str, np.ndarray]]:
        while True:
            yield self.next()

    # -- snapshot/restore (repro.fleet) ---------------------------------

    def state_dict(self) -> Dict[str, object]:
        """The iterator's resumable state: shuffle order, cursor, and the
        rng that generates future epochs' permutations. Restoring it makes
        the stream continue bit-for-bit (`repro.fleet.snapshot`)."""
        return {"order": self._order.copy(), "pos": int(self._pos),
                "rng": self.rng.bit_generator.state}

    def load_state_dict(self, state: Dict[str, object]) -> None:
        self._order = np.asarray(state["order"])
        self._pos = int(state["pos"])
        self.rng.bit_generator.state = state["rng"]


class PublicPool:
    """The shared public unlabeled pool D_* (labels stripped).

    ``sample(step)`` is deterministic in (seed, step) so that *all clients
    draw the same public batch at the same global step* — exactly the
    paper's setup where teachers and students score the same samples. In the
    multi-pod runtime the same property lets each pod materialize the batch
    locally with zero communication (samples are identified by a hash —
    paper §"Communication efficiency").
    """

    def __init__(self, arrays: Arrays, indices: np.ndarray,
                 batch_size: int, seed: int = 0):
        self.resident = isinstance(arrays, DeviceData)
        self.arrays = (arrays.without("labels") if self.resident else
                       {k: v for k, v in arrays.items() if k != "labels"})
        self.indices = np.asarray(indices)
        if self.resident:
            arrays.check(self.indices)
        self.batch_size = batch_size
        self.seed = seed

    def sample(self, step: int) -> Dict[str, np.ndarray]:
        return _take(self.arrays, self.sample_ids(step))

    def sample_ids(self, step: int) -> np.ndarray:
        """Dataset indices of the step-t public batch — the per-sample
        identifiers of the exchange wire format (paper §3.2: samples are
        referenced by hash, never shipped)."""
        rng = np.random.default_rng((self.seed << 20) ^ step)
        return self.indices[rng.integers(0, self.indices.shape[0],
                                         size=self.batch_size)]

    @property
    def size(self) -> int:
        return int(self.indices.shape[0])
