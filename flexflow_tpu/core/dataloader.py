"""Host-side data loading: the SingleDataLoader equivalent.

Reference: python/flexflow_dataloader.{h,cc,cu} + flexflow_cffi.py:2447 —
the full dataset lives in (zero-copy) host memory and `next_batch` copies
each batch shard to the devices. On TPU the shard copy is a `jax.device_put`
with the input's NamedSharding: each host feeds only the shards that live on
its addressable devices (the multi-host analogue of the reference's
per-point-task index launches).
"""

from __future__ import annotations

import queue
import threading
from typing import Dict, Iterator, Optional, Sequence, Tuple

import jax
import numpy as np


class SingleDataLoader:
    """Full-dataset host buffer -> per-batch device arrays for ONE tensor.

    reference flexflow_dataloader.h:34-118 (2D/3D/4D float/int32/int64
    variants — here rank/dtype generic).
    """

    def __init__(
        self,
        ffmodel,
        full_array: np.ndarray,
        batch_size: int,
        sharding=None,
        shuffle: bool = False,
        drop_last: bool = True,
        seed: int = 0,
    ) -> None:
        self.ffmodel = ffmodel
        self.data = np.asarray(full_array)
        self.batch_size = int(batch_size)
        self.sharding = sharding
        self.shuffle = shuffle
        self.drop_last = drop_last
        self._rs = np.random.RandomState(seed)
        self.num_samples = self.data.shape[0]
        if drop_last:
            self.num_batches = self.num_samples // self.batch_size
        else:
            self.num_batches = -(-self.num_samples // self.batch_size)
        self.reset()

    def reset(self) -> None:
        self._next = 0
        self._order = np.arange(self.num_samples)
        if self.shuffle:
            self._rs.shuffle(self._order)

    def next_batch_host(self) -> np.ndarray:
        """Host array for the next batch (wraps around at epoch end) —
        the window-stacking path transfers K of these in one device_put."""
        if self._next >= self.num_batches:
            self.reset()
        i = self._next * self.batch_size
        idx = self._order[i : i + self.batch_size]
        batch = self.data[idx]
        self._next += 1
        return batch

    def next_batch(self):
        """Device array for the next batch (wraps around at epoch end)."""
        from flexflow_tpu.runtime.distributed import device_put_global

        return device_put_global(self.next_batch_host(), self.sharding)

    def __iter__(self) -> Iterator:
        self.reset()
        for _ in range(self.num_batches):
            yield self.next_batch()


class BatchIterator:
    """Zips multiple named arrays into per-step (inputs_dict, label) batches.

    The fit-loop's driver: every tensor advances in lockstep (reference fit
    calls next_batch on every dataloader per iteration,
    flexflow_cffi.py:2058-2100).
    """

    def __init__(
        self,
        inputs: Dict[str, np.ndarray],
        label: Optional[np.ndarray],
        batch_size: int,
        input_shardings: Optional[Dict[str, object]] = None,
        label_sharding=None,
        shuffle: bool = False,
        seed: int = 0,
    ) -> None:
        ns = {a.shape[0] for a in inputs.values()}
        if label is not None:
            ns.add(label.shape[0])
        assert len(ns) == 1, f"inconsistent sample counts: {ns}"
        self.num_samples = ns.pop()
        self.batch_size = int(batch_size)
        self.num_batches = self.num_samples // self.batch_size
        self.loaders = {
            k: SingleDataLoader(
                None,
                v,
                batch_size,
                sharding=(input_shardings or {}).get(k),
                shuffle=False,
                seed=seed,
            )
            for k, v in inputs.items()
        }
        self.label_loader = (
            SingleDataLoader(None, label, batch_size, sharding=label_sharding)
            if label is not None
            else None
        )
        # one shared shuffled order per epoch so inputs/label stay aligned
        self.shuffle = shuffle
        self._rs = np.random.RandomState(seed)
        # one-shot mid-epoch resume cursor (deterministic preemption
        # recovery): the NEXT epoch iteration skips its first N batches
        self._resume_skip = 0

    def reset(self) -> None:
        order = np.arange(self.num_samples)
        if self.shuffle:
            self._rs.shuffle(order)
        for dl in self.loaders.values():
            dl.reset()
            dl._order = order
        if self.label_loader is not None:
            self.label_loader.reset()
            self.label_loader._order = order

    # -- deterministic resume (runtime/checkpoint.py ResumeState) ----------

    def advance_epochs(self, n: int) -> None:
        """Burn `n` completed epochs' shuffle permutations: the shared
        RandomState advances exactly as `n` epoch iterations would have
        advanced it, so a resumed run's epoch-`n` permutation is bitwise
        the uninterrupted run's."""
        for _ in range(int(n)):
            self.reset()

    def set_resume_skip(self, n: int) -> None:
        """Skip the first `n` batches of the NEXT epoch iteration (one
        shot). The skip moves the cursor only — the epoch's permutation is
        drawn in full first, so shuffle order stays identical to a run
        that actually consumed those batches."""
        self._resume_skip = int(n)

    def _begin_epoch(self) -> int:
        self.reset()
        skip = min(self._resume_skip, self.num_batches)
        self._resume_skip = 0
        if skip:
            for dl in self.loaders.values():
                dl._next = skip
            if self.label_loader is not None:
                self.label_loader._next = skip
        return skip

    def __iter__(self):
        skip = self._begin_epoch()
        for _ in range(self.num_batches - skip):
            batch = {k: dl.next_batch() for k, dl in self.loaders.items()}
            label = (
                self.label_loader.next_batch()
                if self.label_loader is not None
                else None
            )
            yield batch, label

    def iter_host(self):
        """Same batches, same shuffle order, but HOST arrays: the fused
        window path stacks K of these and transfers the window in one
        device_put per tensor (shuffle-order parity with __iter__ is what
        makes fused and per-step runs train on identical data)."""
        skip = self._begin_epoch()
        for _ in range(self.num_batches - skip):
            batch = {
                k: dl.next_batch_host() for k, dl in self.loaders.items()
            }
            label = (
                self.label_loader.next_batch_host()
                if self.label_loader is not None
                else None
            )
            yield batch, label


def window_sharding(sharding):
    """The stacked-window sharding of a per-batch input sharding: the
    leading window (scan) dim stays unsharded, the batch sharding's own
    spec shifts one dim right. Works for the DP batch sharding and any
    searched-PCG input sharding alike; None (replicated feed) stays None."""
    if sharding is None:
        return None
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(sharding.mesh, P(None, *sharding.spec))


class _ProducerError:
    def __init__(self, exc: BaseException) -> None:
        self.exc = exc


_PRODUCER_DONE = object()


class WindowedBatchIterator:
    """Double-buffered host->device window pipeline over a BatchIterator.

    Groups `window` consecutive host batches into ONE stacked [k, ...]
    device window per tensor (device_put under the input's window
    sharding), and — when `prefetch` is on — builds + transfers window
    n+1 on a background producer thread while the consumer executes
    window n, so the host-side slice/stack/transfer leaves the step
    loop's critical path. Each transfer is a `host_to_device` span
    (observability/trace.py) on the producer thread's line of the profiler
    trace, beside the consumer's `fit/next_batch` (its wait for a window)
    and `step` spans and over the device's operations: whether the
    transfer hid behind the step is read off that one timeline.

    An epoch's tail (num_batches % window) comes out as one smaller
    window — epoch ends end windows early rather than mixing epochs (a
    window never spans a reshuffle). `keep_host` additionally yields the
    np window stacks (the health localizer's replay input).

    Yields (inputs_stack, label_stack, host_window_or_None, k).
    """

    def __init__(
        self,
        it: BatchIterator,
        window: int,
        keep_host: bool = False,
        prefetch: bool = True,
        fault_channel=None,
        step_base: int = 0,
    ) -> None:
        assert window >= 1
        self.it = it
        self.window = int(window)
        self.keep_host = keep_host
        self.prefetch = prefetch
        # supervision (runtime/supervisor.py): producer-thread deaths are
        # posted here so the consumer — which may be blocked on an empty
        # queue — can surface them instead of waiting forever
        self.fault_channel = fault_channel
        # the global step of the first batch this iterator will yield
        # (the fit loop's _step_count at construction): the chaos
        # schedule's h2d/nonfinite sites key on global steps so the same
        # spec fires at the same data across fresh and resumed runs
        self.step_base = int(step_base)
        self._stop = threading.Event()
        self._queue: Optional[queue.Queue] = None
        self._input_shardings = {
            k: window_sharding(dl.sharding) for k, dl in it.loaders.items()
        }
        self._label_sharding = (
            window_sharding(it.label_loader.sharding)
            if it.label_loader is not None
            else None
        )

    def _windows(self):
        from flexflow_tpu.observability.trace import record_span
        from flexflow_tpu.runtime.distributed import device_put_global
        from flexflow_tpu.runtime.fault import active_schedule

        schedule = active_schedule()
        host_iter = self.it.iter_host()
        steps_built = 0
        pending = True
        while pending:
            if self._stop.is_set():
                # early consumer exit (health raise, recompile trigger):
                # don't build — let alone transfer — another window
                return
            batches = []
            for _ in range(self.window):
                nxt = next(host_iter, None)
                if nxt is None:
                    pending = False
                    break
                batches.append(nxt)
            if not batches:
                return
            k = len(batches)
            if schedule is not None:
                self._inject_window_faults(schedule, batches, steps_built)
            steps_built += k
            host_inputs = {
                name: np.stack([b[0][name] for b in batches])
                for name in batches[0][0]
            }
            host_label = (
                np.stack([b[1] for b in batches])
                if batches[0][1] is not None
                else None
            )
            with record_span("host_to_device", steps=k):
                inputs_stack = {
                    name: device_put_global(arr, self._input_shardings[name])
                    for name, arr in host_inputs.items()
                }
                label_stack = (
                    device_put_global(host_label, self._label_sharding)
                    if host_label is not None
                    else None
                )
            host_win = (host_inputs, host_label) if self.keep_host else None
            yield inputs_stack, label_stack, host_win, k

    def _inject_window_faults(self, schedule, batches, steps_built) -> None:
        """Chaos-schedule sites that live on the producer thread
        (runtime/fault.py): `h2d` kills the producer with an injected
        I/O fault mid-window-build (the death propagates through the
        FaultChannel / queue to the consumer); `nonfinite` poisons the
        firing step's host batch with a NaN BEFORE the device transfer,
        so the run-health policies see a genuinely non-finite step."""
        from flexflow_tpu.runtime.fault import InjectedFault

        first_step = self.step_base + steps_built + 1
        for i in range(len(batches)):
            step = first_step + i
            if schedule.fire_once("h2d", step):
                raise InjectedFault("h2d", step)
            if schedule.fire_once("nonfinite", step):
                inputs_i, _ = batches[i]
                for arr in inputs_i.values():
                    if np.issubdtype(arr.dtype, np.floating):
                        arr.reshape(-1)[0] = np.nan

    def _producer(self):
        try:
            for item in self._windows():
                while not self._stop.is_set():
                    try:
                        self._queue.put(item, timeout=0.05)
                        break
                    except queue.Full:
                        continue
                if self._stop.is_set():
                    return
            self._queue.put(_PRODUCER_DONE)
        except BaseException as e:  # surfaces in the consumer
            # the channel first (non-blocking, survives a full queue and a
            # gone consumer), then the queue so an actively-waiting
            # consumer wakes immediately
            if self.fault_channel is not None:
                self.fault_channel.post("h2d_producer", e)
            try:
                self._queue.put(_ProducerError(e), timeout=5.0)
            except queue.Full:
                pass  # consumer gone or stalled; the channel has it

    def __iter__(self):
        if not self.prefetch:
            yield from self._windows()
            return
        # maxsize=1: exactly one window in flight beyond the one executing
        # (double buffering) — an unbounded queue would race ahead and pin
        # the whole epoch in device memory
        self._queue = queue.Queue(maxsize=1)
        self._stop.clear()
        t = self._thread = threading.Thread(
            target=self._producer, name="ff-input-pipeline", daemon=True
        )
        t.start()
        try:
            while True:
                try:
                    item = self._queue.get(timeout=0.5)
                except queue.Empty:
                    # liveness check: a producer that died WITHOUT posting
                    # a result (hard kill, MemoryError building the error
                    # item) used to leave this get() blocked forever —
                    # the silent-death path the supervision layer closes
                    if not t.is_alive():
                        if self.fault_channel is not None:
                            self.fault_channel.raise_pending(
                                site="h2d_producer"
                            )
                        from flexflow_tpu.runtime.supervisor import (
                            BackgroundFault,
                        )

                        raise BackgroundFault(
                            "h2d_producer",
                            RuntimeError(
                                "input-pipeline producer thread died "
                                "without posting a result"
                            ),
                        )
                    continue
                if item is _PRODUCER_DONE:
                    return
                if isinstance(item, _ProducerError):
                    raise item.exc
                yield item
        finally:
            self.close()

    def close(self) -> None:
        """Unblock and retire the producer (early exit: recompile trigger,
        health `raise`, consumer break)."""
        self._stop.set()
        q = self._queue
        if q is not None:
            try:
                while True:
                    q.get_nowait()
            except queue.Empty:
                pass
        t = getattr(self, "_thread", None)
        if t is not None and t is not threading.current_thread():
            t.join(timeout=5.0)
