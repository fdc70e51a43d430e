"""Batching + device feed: the ``DataLoader`` analog for a sharded world.

The reference builds ``DataLoader(dataset, batch_size=32, sampler=sampler)``
per process (ref dpp.py:35): each rank iterates its sampler shard, 32 rows
at a time, and H2D-copies every batch (ref dpp.py:48).  Global batch is
therefore ``32 × world_size``.

Here one host feeds *all* of its local replicas: the loader walks the
per-replica index shards from ``parallel.sampler``, materializes a host
batch of ``per_replica_batch × local_replicas`` rows (ordered so row-blocks
line up with mesh positions), and ``shard_batch`` places it along the
``data`` mesh axis — single sharded device_put on one host,
``make_array_from_process_local_data`` across hosts.  A one-batch prefetch
overlaps host gather with device compute (the role of DataLoader workers).
"""

from __future__ import annotations

import collections
from typing import Any, Iterator

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributeddataparallel_tpu.observability.trace import get_tracer
from distributeddataparallel_tpu.parallel.sampler import DistributedSampler

Pytree = Any


def _place(batch: Pytree, sharding) -> Pytree:
    """Put a host batch on device — single sharded device_put on one host,
    per-process global-array assembly multi-host.  ``sharding`` is one
    NamedSharding for every leaf, or a pytree of NamedShardings matching
    ``batch`` (mixed-rank batches, e.g. a 1-D validity mask riding along
    2-D token arrays)."""
    if jax.process_count() > 1:
        if isinstance(sharding, NamedSharding):
            sharding = jax.tree.map(lambda _: sharding, batch)
        return jax.tree.map(
            lambda x, s: jax.make_array_from_process_local_data(
                s, np.asarray(x)
            ),
            batch,
            sharding,
        )
    return jax.device_put(batch, sharding)


def shard_batch(batch: Pytree, mesh: Mesh, axis_name: str = "data") -> Pytree:
    """Place a host batch on the mesh, sharded along the data axis.

    The analog of ``data.to(rank)`` (ref dpp.py:48), except one call covers
    every local device and, multi-host, assembles the global array from
    process-local rows.
    """
    return _place(batch, NamedSharding(mesh, P(axis_name)))


def shard_lm_batch(
    tokens,
    mesh: Mesh,
    data_axis: str = "data",
    seq_axis: str = "seq",
    valid=None,
) -> Pytree:
    """Split (B, S+1) host tokens into next-token pairs and shard them
    batch-dim → data axis, seq-dim → seq axis (context parallelism).

    The input/target shift must happen on the host BEFORE sequence
    sharding: position i's target is token i+1, which for the last token
    of a shard lives in the next shard.

    ``valid``: optional (B,) per-row mask (see ``DataLoader(with_mask=)``),
    sharded along the data axis only.
    """
    tokens = np.asarray(tokens)
    batch = {"inputs": tokens[:, :-1], "targets": tokens[:, 1:]}
    sharding: Any = {
        k: NamedSharding(mesh, P(data_axis, seq_axis)) for k in batch
    }
    if valid is not None:
        batch["valid"] = np.asarray(valid, np.float32)
        sharding["valid"] = NamedSharding(mesh, P(data_axis))
    return _place(batch, sharding)


class DataLoader:
    """Iterates (images, labels) batches for this host's replicas.

    Per epoch: for each step, takes ``per_replica_batch`` indices from each
    local replica's sampler shard and concatenates them replica-major, so
    when ``shard_batch`` splits the leading dim across the data axis each
    mesh position receives exactly the rows its DDP-rank counterpart would
    have (ref dpp.py:34-35 semantics, lifted to 1-process-per-host).

    ``drop_last`` defaults to True for training (static shapes for jit —
    a ragged final batch would trigger recompilation; the reference's
    default keeps the ragged batch, torch has no compile cost).
    """

    def __init__(
        self,
        dataset,
        *,
        per_replica_batch: int,
        mesh: Mesh,
        axis_name: str = "data",
        shuffle: bool = True,
        seed: int = 0,
        drop_last: bool = True,
        device_feed: bool = True,
        prefetch: int = 1,
        place_fn=None,
        workers: int = 0,
        with_mask: bool = False,
        augment=None,
        starvation_window: int = 50,
        index_shards=None,
    ):
        """``place_fn(host_batch) -> device_batch`` overrides the default
        data-axis ``shard_batch`` placement (e.g. ``shard_lm_batch`` for
        context parallelism) while keeping the prefetch pipeline.

        ``workers=1`` moves host gather + device placement to a background
        thread (the DataLoader-workers analog, ref dpp.py:35 has none);
        the gather kernels release the GIL in native code, so this
        overlaps input prep with the training loop.  Values > 1 are
        clamped to 1 (batch order is defined by a single producer) with
        a logged warning.

        ``augment(batch, rng) -> batch`` applies training augmentation to
        each host batch (``data.transforms``); its generator is derived
        from (seed, epoch, step, host), so augmentation is deterministic
        across reruns and --resume, and decorrelated across hosts.

        ``with_mask=True`` adds a ``"valid"`` key to every batch: a (rows,)
        float32 mask that is 0 exactly on sampler-padded duplicate rows
        (the ``drop_last=False`` tail padding that keeps per-replica counts
        equal).  Pad slots are a pure function of sampler geometry — local
        position p of replica r maps to global padded-list position
        ``r + p * num_replicas``, and slots >= dataset_len are padding —
        independent of the shuffle, so the mask needs no index bookkeeping.
        Evaluation uses it to compute means over unique samples only
        (``make_eval_step(masked=True)``).
        """
        self.dataset = dataset
        self.per_replica_batch = per_replica_batch
        self.mesh = mesh
        self.axis_name = axis_name
        self.num_replicas = mesh.shape[axis_name]
        self.local_replicas = max(
            1, self.num_replicas // jax.process_count()
        )
        self.host_id = jax.process_index()
        self.shuffle = shuffle
        self.seed = seed
        self.drop_last = drop_last
        self.device_feed = device_feed
        self.prefetch = prefetch
        if workers > 1:
            from distributeddataparallel_tpu.utils.logging import log0

            log0(
                "DataLoader workers=%d clamped to 1 (single ordered "
                "producer thread)", workers,
            )
            workers = 1
        self.workers = workers
        self.with_mask = with_mask
        self._augment = augment
        # Fused augment fast path: one native pass does gather + crop +
        # flip + normalize over the raw uint8 image store.  Gate on the
        # ACTUAL image column dtype — a normalize_u8 dataset with a
        # float image must take the generic augment path, not silently
        # skip augmentation.
        arrays_fn = getattr(dataset, "arrays", None)
        self._fused_augment = bool(
            augment is not None
            and hasattr(augment, "gather_u8")
            and getattr(dataset, "normalize_u8", False)
            and callable(arrays_fn)
            and getattr(arrays_fn().get("image"), "dtype", None) == np.uint8
        )
        self._place_fn = place_fn or (
            lambda b: shard_batch(b, self.mesh, self.axis_name)
        )
        self._epoch = 0
        # Prefetch-pipeline depth for the observability gauge: a zero-arg
        # callable bound by whichever pipeline is active (threaded queue
        # or inline deque); None between iterations.  Reading it is a
        # qsize()/len() call — cheap enough to sample every export.
        self._depth_fn = None
        self.starvation_window = starvation_window
        self._starved_warned = False
        # Optional observability EventLog; when set (dpp.py wires it),
        # starvation emits a structured "loader_starved" record next to
        # the human warning.
        self.events = None

        # Explicit per-replica index shards override the samplers — the
        # elastic-resize path feeds the remainder of an interrupted epoch
        # through here (data.sharded.resize_index_plan), already strided
        # for the NEW replica count.  set_epoch is then a no-op: the
        # shards are one epoch's tail, not a reshuffleable schedule.
        self._index_shards = None
        if index_shards is not None:
            if with_mask:
                raise ValueError(
                    "index_shards + with_mask is unsupported (pad-slot "
                    "masks are a function of sampler geometry)"
                )
            shards_in = [np.asarray(s, np.int64) for s in index_shards]
            if len(shards_in) != self.local_replicas:
                raise ValueError(
                    f"index_shards has {len(shards_in)} rows for "
                    f"{self.local_replicas} local replicas"
                )
            if len({len(s) for s in shards_in}) > 1:
                raise ValueError("index_shards rows must be equal length")
            self._index_shards = shards_in
            self._samplers = []
            per_replica_samples = len(shards_in[0])
        else:
            self._samplers = [
                DistributedSampler(
                    len(dataset),
                    num_replicas=self.num_replicas,
                    rank=self.host_id * self.local_replicas + r,
                    shuffle=shuffle,
                    seed=seed,
                    drop_last=False,
                )
                for r in range(self.local_replicas)
            ]
            per_replica_samples = self._samplers[0].num_samples
        if drop_last:
            self.steps_per_epoch = per_replica_samples // per_replica_batch
        else:
            self.steps_per_epoch = -(-per_replica_samples // per_replica_batch)

    def set_epoch(self, epoch: int) -> None:
        """Reshuffle for a new epoch (analog of ref dpp.py:46)."""
        self._epoch = epoch
        for s in self._samplers:
            s.set_epoch(epoch)

    def __len__(self) -> int:
        return self.steps_per_epoch

    @property
    def prefetch_depth(self) -> int:
        """Batches currently buffered ahead of the consumer (threaded
        queue or inline deque); 0 when no iteration is active.  This is
        the public face of the pipeline's internal buffer — bind it to a
        metrics gauge instead of reaching into the private queue."""
        fn = self._depth_fn
        if fn is None:
            return 0
        try:
            return int(fn())
        except (TypeError, ValueError, NotImplementedError, OSError):
            return 0

    def _gather(self, idx: np.ndarray, image_gather=None) -> Pytree:
        """Materialize rows `idx` as a dict-of-arrays batch.

        Fast path: datasets exposing ``arrays() -> dict[str, np.ndarray]``
        (one fancy-index per column).  Fallback: the generic
        ``__getitem__`` contract — items may be dicts (stacked per key) or
        (image, label) tuples (the torch-Dataset-style pair, ref dpp.py:35).

        ``image_gather(col, idx)`` overrides the uint8 "image" column's
        gather (the fused augment path) — every other column keeps the
        ONE normalize contract defined here.
        """
        gather = getattr(self.dataset, "gather", None)
        if callable(gather) and image_gather is None:
            # Streaming datasets (data.sharded): the dataset owns the
            # shard-aware gather; the loader contract (sampler-ordered
            # rows, normalize-on-access) is the same as the columnar path.
            return gather(idx)
        arrays = getattr(self.dataset, "arrays", None)
        if callable(arrays):
            from distributeddataparallel_tpu import native

            # uint8 image columns with dataset-declared normalization take
            # the fused native gather+normalize kernel (u8 storage = 4x
            # less host RAM; the fused transform measured ~13x faster
            # than gather-then-normalize in NumPy on this path).
            norm = getattr(self.dataset, "normalize_u8", False)
            return {
                k: (
                    image_gather(v, idx)
                    if image_gather is not None
                    and k == "image" and v.dtype == np.uint8
                    else native.gather_normalize_u8(v, idx)
                    if norm and v.dtype == np.uint8 and v.ndim >= 2
                    else v[idx]
                )
                for k, v in arrays().items()
            }
        items = [self.dataset[int(i)] for i in idx]
        if isinstance(items[0], dict):
            return {k: np.stack([it[k] for it in items]) for k in items[0]}
        return {
            "image": np.stack([it[0] for it in items]),
            "label": np.asarray([it[1] for it in items]),
        }

    def _host_batches(self) -> Iterator[Pytree]:
        shards = (
            self._index_shards
            if self._index_shards is not None
            else [s.local_indices() for s in self._samplers]
        )
        B = self.per_replica_batch
        for step in range(self.steps_per_epoch):
            rows, masks = [], []
            for ri, shard in enumerate(shards):
                idx = shard[step * B : (step + 1) * B]
                rows.append(idx)
                if self.with_mask:
                    smp = self._samplers[ri]
                    p = np.arange(step * B, step * B + len(idx))
                    masks.append(
                        smp.rank + p * smp.num_replicas < smp.dataset_len
                    )
            idx_all = np.concatenate(rows)
            rng = (
                np.random.default_rng(
                    (self.seed, 0xA06, self._epoch, step, self.host_id)
                )
                if self._augment is not None
                else None
            )
            if self._fused_augment:
                # One native pass: gather + crop + flip + normalize over
                # the raw uint8 store (transforms.CifarAugment.gather_u8,
                # csrc/ddp_native.cpp) — rng-order-identical to the
                # generic path below.
                batch = self._gather(
                    idx_all,
                    image_gather=lambda v, i: self._augment.gather_u8(
                        v, i, rng
                    ),
                )
            else:
                batch = self._gather(idx_all)
                if self._augment is not None:
                    batch = self._augment(batch, rng)
            if self.with_mask:
                batch["valid"] = np.concatenate(masks).astype(np.float32)
            yield batch

    def _batches(self) -> Iterator[Pytree]:
        """One ``loader.batch`` span round the production of each batch —
        host gather plus, with ``device_feed``, the placement — on
        whichever thread pulls this iterator."""
        tracer = get_tracer()
        it = self._host_batches()
        for _ in range(self.steps_per_epoch):
            with tracer.span("loader.batch"):
                batch = next(it)
                if self.device_feed:
                    batch = self._place_fn(batch)
            yield batch

    def __iter__(self) -> Iterator[Pytree]:
        it = self._batches()
        if not self.device_feed:
            yield from it
            return
        if self.workers > 0:
            yield from self._threaded_iter(it)
            return
        # Software pipeline: keep `prefetch` batches in flight on device so
        # host gather overlaps device compute (DataLoader-workers analog).
        queue: collections.deque = collections.deque()
        self._depth_fn = lambda: len(queue)
        try:
            for batch in it:
                queue.append(batch)
                if len(queue) > self.prefetch:
                    yield queue.popleft()
            while queue:
                yield queue.popleft()
        finally:
            self._depth_fn = None

    def _threaded_iter(self, it: Iterator[Pytree]) -> Iterator[Pytree]:
        """Background-thread pipeline: gather + device placement run off
        the training loop's thread; errors re-raise at the consumer.

        Early consumer exit (step caps, exceptions) sets ``stop``; the
        producer polls it around its bounded put, so the thread winds
        down promptly instead of blocking forever on a full queue.  The
        generator's close path (the ``finally`` below) joins the thread
        with a timeout and re-raises a pending producer exception — a
        consumer that breaks out early must still see the producer's
        failure, not leak a dead thread whose error nobody read."""
        import queue as queue_mod
        import threading

        q: queue_mod.Queue = queue_mod.Queue(maxsize=max(1, self.prefetch))
        done = object()
        stop = threading.Event()
        # The producer parks its exception here as well as in the queue:
        # the queue delivery only works while the consumer is still
        # pulling — on early close the queue is drained blind, and this
        # slot is the only way the error survives to the join.
        pending_error: list[BaseException] = []

        def put(item) -> bool:
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue_mod.Full:
                    continue
            return False

        def produce():
            try:
                for batch in it:
                    if not put(batch):
                        return
                put(done)
            # ddplint: allow[broad-except] — producer thread: transports ANY
            # failure (incl. KeyboardInterrupt) to the consumer via the queue
            except BaseException as e:  # noqa: BLE001 — surface to consumer
                pending_error.append(e)
                put(e)

        t = threading.Thread(target=produce, daemon=True)
        t.start()
        raised = False
        self._depth_fn = q.qsize
        # Starvation signal: count CONSECUTIVE consumer arrivals that
        # find the queue empty.  One empty get is normal pipelining; a
        # full throughput window of them means the producer cannot keep
        # up and the training loop is input-bound — warn once per run.
        empty_streak = 0
        try:
            while True:
                if q.empty():
                    empty_streak += 1
                    if (
                        empty_streak >= self.starvation_window
                        and not self._starved_warned
                    ):
                        self._starved_warned = True
                        from distributeddataparallel_tpu.utils import logging

                        logging.warn_all(
                            "loader prefetch queue empty for %d consecutive "
                            "steps — input pipeline is starving the train "
                            "loop (consider more workers or faster storage)",
                            empty_streak,
                        )
                        if self.events is not None:
                            self.events.emit(
                                "loader_starved",
                                window=empty_streak,
                                epoch=self._epoch,
                            )
                else:
                    empty_streak = 0
                item = q.get()
                if item is done:
                    break
                if isinstance(item, BaseException):
                    raised = True
                    raise item
                yield item
        finally:
            self._depth_fn = None
            stop.set()
            while not q.empty():  # release buffers the producer parked
                q.get_nowait()
            t.join(timeout=5.0)
            if t.is_alive():
                from distributeddataparallel_tpu.utils.logging import (
                    warn_all,
                )

                warn_all(
                    "loader producer thread failed to stop within 5s of "
                    "generator close; leaking a daemon thread"
                )
            # Early consumer exit (GeneratorExit / step cap): the
            # producer may have died with an exception the __next__ path
            # never delivered.  Re-raise it here — unless this close IS
            # the unwind of that very exception propagating from the
            # raise above.
            if pending_error and not raised:
                raise pending_error[0]
