"""In-memory datasets + synthetic data for tests/benchmarks.

Ref: /root/reference/python/paddle/fluid/dataset.py (InMemoryDataset /
QueueDataset for PS training over files) and python/paddle/dataset/* builtin
dataset loaders. Here: a light InMemoryDataset with global-shuffle semantics
plus synthetic generators used by tests and examples (no network egress).
"""

import numpy as np


class InMemoryDataset:
    """ref: dataset.py InMemoryDataset — load → (global) shuffle → iterate.
    The reference shuffles via fleet RPC across trainers; here shuffling is
    host-local per process, and multi-host global shuffle is done by seeding
    identically and partitioning by rank (ref: data_set.cc global_shuffle)."""

    def __init__(self, samples=None):
        self._samples = list(samples) if samples is not None else []

    def load(self, samples):
        self._samples.extend(samples)

    def global_shuffle(self, seed=0, rank=0, world=1):
        rng = np.random.RandomState(seed)
        idx = rng.permutation(len(self._samples))
        part = idx[rank::world]
        self._samples = [self._samples[i] for i in part]
        return self

    def reader(self):
        def r():
            yield from self._samples
        return r

    def readers(self, n):
        """n shard readers (round-robin) for multi-threaded ingestion
        (ref data_feed.cc: one DataFeed per DeviceWorker thread)."""
        m = max(n, 1)

        def make(i):
            def r():
                yield from self._samples[i::m]
            return r
        return [make(i) for i in range(m)]

    def __len__(self):
        return len(self._samples)


def synthetic_images(n, shape=(3, 32, 32), num_classes=10, seed=0):
    """CIFAR-like synthetic stream (tests/bench; the reference's book tests
    download CIFAR — zero-egress here)."""
    rng = np.random.RandomState(seed)
    for _ in range(n):
        yield (rng.rand(*shape).astype(np.float32),
               rng.randint(num_classes, size=(1,)).astype(np.int64))


def synthetic_mnist(n, seed=0):
    rng = np.random.RandomState(seed)
    for _ in range(n):
        yield (rng.rand(1, 28, 28).astype(np.float32),
               rng.randint(10, size=(1,)).astype(np.int64))


def synthetic_tokens(n, seq_len=128, vocab=30522, seed=0):
    """BERT-like token stream."""
    rng = np.random.RandomState(seed)
    for _ in range(n):
        ids = rng.randint(vocab, size=(seq_len,)).astype(np.int32)
        yield (ids,)


def synthetic_ctr(n, num_sparse=26, num_dense=13, vocab=10000, seed=0):
    """Criteo-like CTR stream for DeepFM/Wide&Deep (ref: dist_ctr.py
    fixture)."""
    rng = np.random.RandomState(seed)
    for _ in range(n):
        dense = rng.rand(num_dense).astype(np.float32)
        sparse = rng.randint(vocab, size=(num_sparse,)).astype(np.int32)
        label = rng.randint(2, size=(1,)).astype(np.float32)
        yield (dense, sparse, label)


class FileDataset:
    """File-backed dataset over the native (C++) record reader — the
    DataFeed/Dataset successor for real file ingestion (ref data_feed.cc
    MultiSlotDataFeed reading file lists into channels; dataset.py
    QueueDataset).

    samples are numpy-record blobs (data/native.numpy_records); readers(n)
    shards the FILE LIST across ingestion threads like the reference
    assigns filelists to DataFeed instances.
    """

    def __init__(self, files, num_threads=2, decode=None):
        from paddle_tpu.core.enforce import enforce
        from paddle_tpu.data import native
        enforce(len(list(files)) > 0, "FileDataset needs at least one file")
        self._native = native
        self.files = list(files)
        self.num_threads = num_threads
        self.decode = decode or native.unpack_numpy_record

    def _read(self, files, num_threads):
        # remote (gs://-like) entries are staged to the local cache at
        # read time — the C++ reader needs real POSIX paths (ref fs.cc's
        # download-to-tmp pattern); local paths pass through untouched.
        # Shards download CONCURRENTLY (num_threads-wide, matching the
        # reader's own parallelism) so first-record latency is bounded by
        # the largest shard, not the sum.
        from paddle_tpu.io import fs as _fs
        if any(_fs.split_scheme(f)[0] is not None for f in files):
            from concurrent.futures import ThreadPoolExecutor
            with ThreadPoolExecutor(max_workers=max(num_threads, 1)) as ex:
                files = list(ex.map(_fs.ensure_local, files))
        rd = self._native.NativeRecordReader(files, num_threads=num_threads)
        try:
            for rec in rd:
                yield self.decode(rec)
        finally:
            rd.close()  # release C++ reader threads + ring on any exit

    def reader(self):
        return lambda: self._read(self.files, self.num_threads)

    def readers(self, n):
        """min(n, len(files)) shard readers; each shard's native reader
        uses `num_threads` internal threads (total native threads =
        shards x num_threads)."""
        m = max(min(n, len(self.files)), 1)
        return [
            (lambda i=i: self._read(self.files[i::m], self.num_threads))
            for i in range(m)
        ]
