"""Background-thread host→device prefetch.

Replaces the reference's 40-worker torch DataLoader (reference:
train.py:33-41): feature loading + collate run on one worker thread while
the device computes, and under a mesh finished batches are device_put with
its batch sharding ahead of time so each step starts with data already in
HBM. One thread is enough because the dataset keeps every sample it has
read (data/dataset.py, ``CacheBudget``): only a run's first epoch, and what
of a corpus does not fit the host's memory, is paced by the file system.

Shutdown contract (ISSUE 2 hardening): the worker only ever blocks on a
*stop-aware bounded put* (it polls the stop event while the queue is
full, so ``stop()`` can never strand it), and it enqueues exactly one
terminal item — either a clean end-of-stream or the error that killed
the source — never both. ``stop()`` drains, joins the worker, and is
idempotent; the class is also a context manager so short-lived
prefetchers (validation passes) cannot leak their thread.

The worker's spans (obs/trace.py): ``loader_h2d`` around each batch's
host→device transfer where the worker makes one (a mesh is given; on
the single-chip path the host arrays go to the device inside the jitted
call, under the step loop's ``train_dispatch``, and no ``loader_h2d`` is
opened), and ``loader_blocked`` around the put of a batch that found the
queue full: the loader waiting for the device. Blocked time near zero
while the step loop's ``train_data_wait`` is not means the loader sets
the pace; the other way round, the device does.
"""

import queue
import threading
from typing import Iterator, Optional

import jax

from speakingstyle_tpu.data.dataset import Batch
from speakingstyle_tpu.obs import MetricsRegistry, Span, get_registry
from speakingstyle_tpu.parallel.mesh import batch_sharding
from speakingstyle_tpu.training.resilience import retry_io


class Terminal:
    """The single end-of-stream marker; ``error`` is None for a clean end.

    Shared with the serving admission queue (serving/batcher.py): any
    bounded producer/consumer pair in this codebase signals end-of-stream
    with exactly one of these, never a sentinel-less close.
    """

    __slots__ = ("error",)

    def __init__(self, error: Optional[BaseException] = None):
        self.error = error


def bounded_put(q: "queue.Queue", item, stopped: threading.Event,
                poll: float = 0.05) -> bool:
    """Bounded put that can never outlive a stop: polls ``stopped`` while
    the queue is full. Returns False if stopped before enqueueing.

    The load-bearing shutdown primitive shared by DevicePrefetcher and
    the serving batcher — a plain ``Queue.put`` on a full queue blocks
    forever if the consumer died, stranding the producer thread.
    """
    while not stopped.is_set():
        try:
            q.put(item, timeout=poll)
            return True
        except queue.Full:
            continue
    return False


class DevicePrefetcher:
    """Wrap a host batch iterator; yield (Batch, device_arrays) pairs."""

    def __init__(
        self,
        batches: Iterator[Batch],
        mesh=None,
        depth: int = 2,
        transfer_retries: int = 0,
        transfer_backoff: float = 0.05,
        registry: Optional[MetricsRegistry] = None,
    ):
        self.batches = batches
        self.sharding = batch_sharding(mesh) if mesh is not None else None
        self.queue: "queue.Queue" = queue.Queue(maxsize=depth)
        self.transfer_retries = transfer_retries
        self.transfer_backoff = transfer_backoff
        self.registry = registry if registry is not None else get_registry()
        self._batches_ctr = self.registry.counter(
            "data_prefetch_batches_total",
            help="batches handed to the step loop",
        )
        self._stopped = threading.Event()
        self._finished = False
        self.thread = threading.Thread(
            target=self._worker, name="prefetch-worker", daemon=True
        )
        self.thread.start()

    def _put(self, batch: Batch):
        arrays = batch.arrays()
        if self.sharding is not None:
            if jax.process_count() > 1:
                # Multi-host: every process builds the identical global
                # batch (same dataset + seed => same shuffle), and each
                # host materializes only its addressable shards. XLA then
                # treats the result as one global array over the pod mesh.
                # make_array_from_process_local_data slices the local data
                # per the sharding itself; global_shape == local shape
                # tells it each process holds the FULL batch
                arrays = {
                    k: jax.make_array_from_process_local_data(
                        self.sharding, v, global_shape=v.shape
                    )
                    for k, v in arrays.items()
                }
            else:
                # single-process: one device_put against the batch
                # NamedSharding (never a hard-pinned device — jaxlint
                # JL014 guards that under training/ and data/)
                arrays = {
                    k: jax.device_put(v, self.sharding)
                    for k, v in arrays.items()
                }
        return batch, arrays

    def _transfer(self, batch: Batch):
        """Host→device transfer with retry-with-backoff on transient
        runtime errors (re-entrant, unlike the source iterator). Without a
        sharding there is none to make: the jitted call moves the host
        arrays, and no ``loader_h2d`` span is opened."""
        if self.sharding is None:
            return self._put(batch)
        with Span("loader_h2d", registry=self.registry,
                  bytes=sum(a.nbytes for a in batch.arrays().values())):
            if not self.transfer_retries:
                return self._put(batch)
            return retry_io(
                lambda: self._put(batch),
                retries=self.transfer_retries,
                backoff=self.transfer_backoff,
                exceptions=(OSError, jax.errors.JaxRuntimeError),
                describe="device transfer",
            )

    def _bounded_put(self, item) -> bool:
        """Stop-aware bounded put (see module-level ``bounded_put``); the
        wait on a full queue is a ``loader_blocked`` span. The worker is
        the queue's one producer, so a queue that is not full takes the
        item without a wait."""
        if not self.queue.full():
            return bounded_put(self.queue, item, self._stopped)
        with Span("loader_blocked", registry=self.registry):
            return bounded_put(self.queue, item, self._stopped)

    def _worker(self):
        terminal = Terminal()
        try:
            for batch in self.batches:
                if self._stopped.is_set():
                    return
                if not self._bounded_put(self._transfer(batch)):
                    return
        except BaseException as e:  # surfaced on the consumer side
            terminal = Terminal(e)
        self._bounded_put(terminal)

    def __iter__(self):
        return self

    def __next__(self):
        if self._finished:
            raise StopIteration
        item = self.queue.get()
        if isinstance(item, Terminal):
            self._finished = True
            if item.error is not None:
                raise item.error
            raise StopIteration
        self._batches_ctr.inc()
        return item

    def stop(self):
        """Idempotent: unblock + join the worker and drain the queue."""
        self._stopped.set()
        # drain so a worker blocked in _bounded_put unblocks promptly
        try:
            while True:
                self.queue.get_nowait()
        except queue.Empty:
            pass
        self.thread.join(timeout=5.0)

    def __enter__(self) -> "DevicePrefetcher":
        return self

    def __exit__(self, *exc) -> bool:
        self.stop()
        return False
