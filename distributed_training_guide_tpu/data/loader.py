"""Distributed batch loader.

Parity with the reference's ``DataLoader + DistributedSampler``
(``02-distributed-data-parallel/train_llm.py:76-84``):

- deterministic per-epoch shuffle keyed by (seed, epoch) — ``set_epoch``
  (``02:137``);
- ``drop_last`` partitioning into global batches;
- each process only materializes the shards its local devices own, assembled
  into one global ``jax.Array`` via ``make_array_from_callback`` (the JAX
  analogue of per-rank sampler index partitioning — under a (dp, tp) mesh the
  tp group automatically reads identical data because the batch dim is only
  sharded over the data axes, which the reference has to hand-arrange with a
  mesh-aware sampler, ``06-tensor-parallel/train_llm.py:141-147``);
- epoch fast-forward for resume (``01:133-135``) via ``start_step``.

Double-buffered host->device prefetch hides dispatch latency (reference C26,
``related-topics/optimizing-data-loading``).
"""
from __future__ import annotations

from typing import Iterator, Optional

import jax
import numpy as np

from ..utils.trace import span


class ShardedBatchLoader:
    def __init__(
        self,
        dataset: np.ndarray,          # [num_seqs, seq_len] int32
        global_batch_size: int,
        sharding,                      # NamedSharding for [B, S] (or [A, B, S])
        *,
        grad_accum: int = 1,
        seed: int = 0,
        shuffle: bool = True,
        prefetch: int = 2,
        native: bool = False,
    ):
        if global_batch_size % max(grad_accum, 1) != 0:
            raise ValueError("global_batch_size must be divisible by grad_accum")
        self.dataset = dataset
        self.global_batch_size = global_batch_size
        self.sharding = sharding
        self.grad_accum = grad_accum
        self.seed = seed
        self.shuffle = shuffle
        self.prefetch = prefetch
        self.epoch = 0
        self._native = None
        self._native_path = None
        if native:
            if not shuffle:
                import logging

                logging.getLogger(__name__).warning(
                    "native loader has no unshuffled mode; using python assembly")
            else:
                self._native = self._make_native()

    def set_epoch(self, epoch: int) -> None:
        self.epoch = epoch

    def __len__(self) -> int:
        return len(self.dataset) // self.global_batch_size

    def _epoch_order(self) -> np.ndarray:
        order = np.arange(len(self.dataset))
        if self.shuffle:
            np.random.RandomState(self.seed + 1000003 * self.epoch).shuffle(order)
        return order

    def _leading_shape(self) -> tuple:
        if self.grad_accum > 1:
            return (self.grad_accum, self.global_batch_size // self.grad_accum)
        return (self.global_batch_size,)

    def _make_global_array(self, np_batch: np.ndarray) -> jax.Array:
        """Global array from an already-assembled host batch (the native
        path: the C++ loader hands back the full batch by contract)."""
        np_batch = np_batch.reshape(self._leading_shape() + np_batch.shape[-1:])
        with span("data.put"):
            return jax.make_array_from_callback(
                np_batch.shape, self.sharding, lambda idx: np_batch[idx])

    def _assemble_batch(self, idx: np.ndarray) -> jax.Array:
        """Global array materializing ONLY the rows this process's devices
        own (reference C26, ``related-topics/optimizing-data-loading/
        README.md:24-102``): the callback fancy-indexes the — possibly
        disk-backed — dataset per addressable shard, so per-host RAM is the
        local share of each batch, never the global batch (and never the
        corpus, when the dataset is a memmap)."""
        # sorted for memmap read locality only: which sequences form the
        # batch is shuffled (the caller's epoch order); their within-batch
        # order is deliberately left ascending — example->device-slot
        # assignment carries no semantics (grads sum over the batch)
        idx_nd = np.sort(idx).reshape(self._leading_shape())
        seq = self.dataset.shape[1]

        def fetch(shard_index):
            with span("data.assemble"):   # a child of data.put: the callback
                sel = idx_nd[shard_index[:-1]]
                rows = np.asarray(self.dataset[sel.ravel()], dtype=np.int32)
                return rows.reshape(sel.shape + (seq,))[..., shard_index[-1]]

        with span("data.put"):
            return jax.make_array_from_callback(
                idx_nd.shape + (seq,), self.sharding, fetch)

    def _native_compatible_backing(self):
        """Path of the dataset's own backing file when the C++ loader can
        mmap it directly (raw int32 token-file layout covering the whole
        file) — the zero-copy path; None forces a temp-file copy."""
        import os

        ds = self.dataset
        filename = getattr(ds, "filename", None)
        if (isinstance(ds, np.memmap) and filename
                and ds.dtype == np.int32 and ds.flags["C_CONTIGUOUS"]
                and getattr(ds, "offset", 1) == 0
                and ds.size * 4 == os.path.getsize(filename)):
            return filename
        return None

    def _make_native(self):
        """Back batch assembly with the C++ loader (csrc/token_loader.cpp):
        mmap + worker threads + bounded prefetch, no GIL. A memmap dataset in
        the raw token-file layout (``--mmap-data``) is mmap'd IN PLACE — no
        second on-disk copy of the corpus (reference C26). Raises when the
        library cannot be built: ``--native-loader`` was asked for."""
        import tempfile

        from .native_loader import NativeTokenLoader, write_token_file

        path = self._native_compatible_backing()
        if path is None:
            tmp = tempfile.NamedTemporaryFile(suffix=".tokens.bin", delete=False)
            tmp.close()  # the C++ side reopens by path; don't leak the fd
            self._native_path = tmp.name   # ours: unlinked on close()
            write_token_file(self.dataset, tmp.name)
            path = tmp.name
        return NativeTokenLoader(path, seq_len=self.dataset.shape[1],
                                 batch=self.global_batch_size, seed=self.seed,
                                 prefetch=max(self.prefetch, 2))

    def close(self) -> None:
        if self._native is not None:
            self._native.close()
            self._native = None
        if self._native_path is not None:
            import os

            try:
                os.unlink(self._native_path)
            except OSError:
                pass
            self._native_path = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    def epoch_batches(self, start_step: int = 0) -> Iterator[dict]:
        """Yields {'input_ids', 'labels'} global jax.Arrays; skips the first
        ``start_step`` batches while preserving data order (resume)."""
        if self._native is not None:
            # same pending-queue H2D overlap as the python path, on top of the
            # C++ assembly prefetch
            pending: list[dict] = []
            native = iter(self._native.epoch_batches(self.epoch, start_step))
            while True:
                with span("data.assemble"):   # the wait for the C++ workers
                    np_batch = next(native, None)
                if np_batch is None:
                    break
                ids = self._make_global_array(np_batch)
                pending.append({"input_ids": ids, "labels": ids})
                if len(pending) > self.prefetch:
                    yield pending.pop(0)
            yield from pending
            return
        order = self._epoch_order()
        n = len(self)
        pending: list[dict] = []
        for step in range(start_step, n):
            idx = order[step * self.global_batch_size:(step + 1) * self.global_batch_size]
            ids = self._assemble_batch(idx)
            pending.append({"input_ids": ids, "labels": ids})
            if len(pending) > self.prefetch:
                yield pending.pop(0)
        yield from pending
