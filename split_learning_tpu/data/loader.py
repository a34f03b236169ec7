"""Label-count-driven dataset subsetting + batching.

Parity surface: the reference's ``data_loader(data_name, batch_size,
distribution, train)`` (``/root/reference/src/dataset/dataloader.py:124-133``)
where ``distribution`` is a per-label sample-count vector and each loader
samples exactly that many examples per class (``:61-92``).

TPU-first differences:

* batches are numpy arrays with **static shapes** (``drop_last`` semantics:
  a trailing partial batch would retrigger XLA compilation, so it is folded
  by wrapping around the shuffled epoch instead of being emitted ragged);
* a batch is assembled in ONE pass: every sample is written once on the
  host, from the data set's array into the array that goes to the device
  (a fresh one a batch from ``__iter__``, or a slot of the caller's
  through :meth:`Epoch.fill`).  Augmentation (random crop + horizontal
  flip for CIFAR) is an index transform inside that gather, pure numpy,
  not a pass of its own.  Without augmentation nothing is reordered: a
  batch has the data set's memory order (the CIFAR files' planes stay
  planes), which is also what the device upload reads fastest;
* nothing here runs beside the device on its own: the overlap with
  device compute comes from the caller's asynchronous step call, which
  returns while the device works and so lets the next batch be built;
* everything is seeded and deterministic.
"""

from __future__ import annotations

import dataclasses
import zlib
from typing import Callable

import numpy as np


def subset_seed(base_seed: int, client_key: str, round_idx: int = 0,
                refresh: bool = False) -> int:
    """Loader seed for one client's label-count subset draw.

    crc32, not ``hash()`` (salted per process): two clients with
    identical label counts must still draw DISTINCT subsets, and the
    same deployment must draw the same ones on every run.  With
    ``refresh`` (the reference's ``data-distribution.refresh`` —
    clients rebuild their loader every round, ``src/RpcClient.py:108``)
    the seed also varies per round, re-sampling the subset."""
    s = (zlib.crc32(client_key.encode()) ^ base_seed) % (2 ** 31)
    if refresh:
        s = (s ^ (0x9E3779B1 * (round_idx + 1))) % (2 ** 31)
    return s


def label_count_subset(labels: np.ndarray, counts: np.ndarray,
                       rng: np.random.Generator) -> np.ndarray:
    """Indices selecting exactly ``counts[c]`` examples of each class c.

    If a class has fewer examples than requested, sampling wraps with
    replacement (the reference errors out instead; wrapping keeps synthetic
    smoke datasets usable at any requested scale).
    """
    idx: list[np.ndarray] = []
    for c, n in enumerate(np.asarray(counts, dtype=int)):
        if n <= 0:
            continue
        pool = np.nonzero(labels == c)[0]
        if len(pool) == 0:
            continue
        replace = len(pool) < n
        idx.append(rng.choice(pool, size=n, replace=replace))
    if not idx:
        return np.empty((0,), dtype=int)
    out = np.concatenate(idx)
    rng.shuffle(out)
    return out


def _memory_order(a: np.ndarray) -> tuple | None:
    """The axes of ``a`` from the slowest to the fastest in memory, the
    sample axis first, if ``a`` is C-contiguous when read in that order
    (the identity for a C-contiguous array; ``(0, 3, 1, 2)`` for the
    CIFAR files' planes viewed as NHWC); ``None`` if in no order."""
    perm = (0,) + tuple(sorted(range(1, a.ndim),
                               key=lambda k: -a.strides[k]))
    return perm if a.transpose(perm).flags.c_contiguous else None


def _map_inputs(f, inputs):
    """``f`` over ``inputs``: one array, or a dict of arrays key by key."""
    if isinstance(inputs, dict):
        return {k: f(v) for k, v in inputs.items()}
    return f(inputs)


@dataclasses.dataclass
class ArrayDataset:
    """In-memory dataset: ``inputs`` is one array or a dict of arrays
    (e.g. BERT's input_ids/attention_mask), ``labels`` is int."""
    inputs: np.ndarray | dict
    labels: np.ndarray

    def __len__(self) -> int:
        return len(self.labels)

    def take(self, idx: np.ndarray) -> "ArrayDataset":
        return ArrayDataset(_map_inputs(lambda v: v[idx], self.inputs),
                            self.labels[idx])


class DataLoader:
    """Seeded shuffling batcher with static batch shapes.

    ``augment(data, rng, idx, out)`` writes the augmented ``data[idx]``
    into ``out`` (:func:`cifar_augment`'s contract); without one a batch
    is ``np.take`` into ``out``.  Iterating yields ``(inputs, labels)``
    in fresh arrays; ``len()`` is batches/epoch.  ``iter()`` gives an
    :class:`Epoch`, whose ``fill`` writes into the caller's arrays.
    """

    def __init__(self, dataset: ArrayDataset, batch_size: int,
                 shuffle: bool = True,
                 augment: Callable[[np.ndarray, np.random.Generator,
                                    np.ndarray, np.ndarray],
                                   object] | None = None,
                 seed: int = 0):
        if len(dataset) == 0:
            raise ValueError("empty dataset")
        def settle(a):
            # ``np.take`` copies a source it cannot read as C-contiguous
            # WHOLE before it gathers, in every batch: such an array is
            # copied here, once.  One that is contiguous in another order
            # of its axes stays as it is and is gathered in that order
            # (``_memory_order``); an augmentation reads plain C order.
            if augment is not None or _memory_order(a) is None:
                return np.ascontiguousarray(a)
            return a
        self.dataset = ArrayDataset(_map_inputs(settle, dataset.inputs),
                                    dataset.labels)
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.augment = augment
        self._rng = np.random.default_rng(seed)
        self.num_batches = max(1, len(dataset) // batch_size)

    @property
    def samples_per_epoch(self) -> int:
        return self.num_batches * self.batch_size

    def __len__(self) -> int:
        return self.num_batches

    def __iter__(self) -> "Epoch":
        return Epoch(self)

    def empty(self, lead: tuple = (), label_dtype=None) -> tuple:
        """Uninitialised ``(inputs, labels)`` for ``lead`` batches: each
        array ``lead + (batch_size,) + sample shape``, one a key for dict
        inputs, its sample axes in the data set's memory order (a batch
        leaves for the device laid out as the data set is); labels in
        the data set's dtype or ``label_dtype``."""
        n, bs = len(lead), self.batch_size

        def like(a):
            perm = _memory_order(a)
            base = np.empty(lead + (bs,) + tuple(a.shape[k]
                                                 for k in perm[1:]), a.dtype)
            return base.transpose(tuple(range(n)) + tuple(
                n + int(k) for k in np.argsort(perm)))
        labels = self.dataset.labels
        return (_map_inputs(like, self.dataset.inputs),
                np.empty(lead + (bs,) + labels.shape[1:],
                         label_dtype or labels.dtype))

    def _epoch_order(self) -> np.ndarray:
        n = len(self.dataset)
        order = np.arange(n)
        if self.shuffle:
            self._rng.shuffle(order)
        need = self.num_batches * self.batch_size
        if n < need:
            # wrap (with repetition for tiny datasets) to fill the static
            # batch shape
            reps = -(-need // n)
            order = np.tile(order, reps)[:need]
        return order

    def _gather(self, idx: np.ndarray, out_x, out_y, slot: tuple) -> None:
        """The one write of a batch: samples ``idx`` into ``out_x[slot]``
        (key by key for dict inputs), their labels into ``out_y[slot]``."""
        ins = self.dataset.inputs
        if isinstance(ins, dict):
            pairs = [(v, out_x[k][slot]) for k, v in ins.items()]
        else:
            pairs = [(ins, out_x[slot])]
        for data, out in pairs:
            if self.augment is not None:
                self.augment(data, self._rng, idx, out)
            else:
                # idx comes from arange(n): "clip" never clips, and unlike
                # the default "raise" it does not buffer ``out``
                perm = _memory_order(data)
                np.take(data.transpose(perm), idx, axis=0,
                        out=out.transpose(perm), mode="clip")
        out_y[slot] = self.dataset.labels[idx]


class Epoch:
    """One pass over a :class:`DataLoader`: an iterator of fresh
    ``(inputs, labels)`` batches whose :meth:`fill` writes the next batch
    into arrays of the caller instead.  The epoch's shuffle is drawn
    from the loader's generator at the first batch, not at ``iter()``."""

    def __init__(self, loader: DataLoader):
        self._loader = loader
        self._order = None
        self._b = 0

    def __iter__(self) -> "Epoch":
        return self

    def _next_idx(self) -> np.ndarray:
        ld = self._loader
        if self._order is None:
            self._order = ld._epoch_order()
        if self._b >= ld.num_batches:
            raise StopIteration
        self._b += 1
        return self._order[(self._b - 1) * ld.batch_size:
                           self._b * ld.batch_size]

    def __next__(self) -> tuple:
        idx = self._next_idx()
        out_x, out_y = self._loader.empty()
        self._loader._gather(idx, out_x, out_y, ())
        return out_x, out_y

    def fill(self, out_x, out_y, slot: tuple = ()) -> None:
        """Write the next batch's inputs into ``out_x[slot]`` (for dict
        inputs ``out_x[key][slot]``) and its labels into ``out_y[slot]``;
        ``StopIteration`` when the epoch is over, with nothing written.
        The arrays are :meth:`DataLoader.empty`'s (the data set's input
        dtype and memory order; others are filled through a copy)."""
        self._loader._gather(self._next_idx(), out_x, out_y, slot)


def cifar_augment(x: np.ndarray, rng: np.random.Generator,
                  idx: np.ndarray | None = None,
                  out: np.ndarray | None = None) -> np.ndarray:
    """Random crop (pad 4) + horizontal flip, NHWC — the reference's
    torchvision transform pipeline (``src/dataset/dataloader.py:63-70``)
    in numpy — of ``x[idx]`` (all of ``x`` without ``idx``), written into
    ``out`` when given.

    One gather and no padded copy: from the draws (per image a row offset,
    a column offset, a flip, in that order) it computes for every output
    pixel its source row and column in the UNPADDED image — a reflection
    that does not repeat the edge, as ``np.pad(mode="reflect")`` has it:
    ``i -> |i - 4|``, then ``2(n-1) - i`` where that is ``>= n`` (so
    ``h, w >= 5``); a flip reverses the columns — and takes the pixels
    straight from ``x`` into ``out``."""
    n, h, w = x.shape[:3]
    first = np.arange(n) if idx is None else np.asarray(idx)
    if first.size and (first.min() < 0 or first.max() >= n):
        raise IndexError("cifar_augment: idx out of range")
    b = len(first)
    ys = rng.integers(0, 9, size=b)
    xs = rng.integers(0, 9, size=b)
    flip = rng.random(b) < 0.5

    def reflect(i, size):
        i = np.abs(i - 4)
        return np.where(i >= size, 2 * (size - 1) - i, i)

    rows = reflect(ys[:, None] + np.arange(h), h)
    cols = reflect(xs[:, None] + np.arange(w), w)
    cols = np.where(flip[:, None], cols[:, ::-1], cols)
    pixel = ((first[:, None] * h + rows)[:, :, None] * w
             + cols[:, None, :]).reshape(-1)
    if out is None:
        out = np.empty((b,) + x.shape[1:], x.dtype)
    flat_out = out.view()
    flat_out.shape = (b * h * w, -1)  # raises, never copies, if strided
    # every index is in range by construction: "clip" never clips, and
    # unlike the default "raise" it does not buffer ``out``
    np.take(np.ascontiguousarray(x).reshape(n * h * w, -1), pixel, axis=0,
            out=flat_out, mode="clip")
    return out
