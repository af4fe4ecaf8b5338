"""Async device-prefetch loader.

The reference overlaps input with compute via double_buffer /
prefetch ops inside its C++ reader chain (reference
paddle/fluid/operators/reader/create_double_buffer_reader_op.cc). The
TPU-native equivalent lives on the host side of the PJRT boundary: a
background thread runs the (possibly C++-recordio-backed) reader and
``jax.device_put``s batches one-or-more steps ahead, so the
host→device transfer of batch N+1 rides under the device compute of
batch N. Because jax dispatch is async, the Executor can consume the
already-resident arrays without ever blocking on the wire.
"""
import os
import queue
import threading

import numpy as np

from ..resilience.retry import default_policy, with_retries

__all__ = ["DeviceLoader"]

_END = object()


class DeviceLoader:
    """Wraps ``reader`` (a generator fn of feed dicts, or of tuples to
    be zipped with ``feed_names``) and yields dicts of device-resident
    arrays, transferred ``buffer_size`` batches ahead by a background
    thread.

    with DeviceLoader(reader, feed_names=["img", "label"]) as dl:
        for feed in dl:
            exe.run(main, feed=feed, fetch_list=[loss])

    Resilience (docs/RELIABILITY.md): ``reader_retries`` > 1 wraps the
    source in ``reader.retry_reader`` (IOError-class failures retried
    with exponential backoff; default from PADDLE_TPU_READER_RETRIES,
    1 = off), and each host→device transfer runs under the shared
    transient-device retry policy — a dropped PJRT connection during
    prefetch re-sends the batch instead of killing the epoch.
    """

    def __init__(self, reader, feed_names=None, buffer_size=2,
                 device=None, reader_retries=None, skip_budget=0):
        if buffer_size < 1:
            raise ValueError("buffer_size must be >= 1")
        if reader_retries is None:
            reader_retries = int(
                os.environ.get("PADDLE_TPU_READER_RETRIES", "1"))
        if reader_retries > 1 or skip_budget > 0:
            from ..reader import retry_reader
            reader = retry_reader(reader,
                                  max_attempts=max(1, reader_retries),
                                  skip_budget=skip_budget)
        self._reader = reader
        self._feed_names = feed_names
        self._buffer = buffer_size
        self._device = device
        self._thread = None
        self._queue = None
        self._stop = threading.Event()
        self._error = None

    # ------------------------------------------------------------------
    def _to_feed_dict(self, item):
        if isinstance(item, dict):
            return item
        if self._feed_names is None:
            raise ValueError(
                "reader yields tuples — pass feed_names to map them")
        if len(item) != len(self._feed_names):
            raise ValueError(
                f"reader yielded {len(item)} fields for "
                f"{len(self._feed_names)} feed names")
        return dict(zip(self._feed_names, item))

    def _worker(self):
        import jax
        policy = default_policy()

        def _put(arr):
            # transient transfer failures (connection reset mid-prefetch)
            # re-send the batch under the shared retry policy
            return with_retries(
                lambda: (jax.device_put(arr, self._device)
                         if self._device is not None
                         else jax.device_put(arr)),
                policy=policy)

        try:
            for item in self._reader():
                if self._stop.is_set():
                    return
                feed = self._to_feed_dict(item)
                staged = {}
                for k, v in feed.items():
                    arr = np.asarray(v) if not isinstance(v, jax.Array) \
                        else v
                    staged[k] = _put(arr)
                self._queue.put(staged)
            self._queue.put(_END)
        except BaseException as e:                 # surfaced on next()
            self._error = e
            self._queue.put(_END)

    # ------------------------------------------------------------------
    def start(self):
        if self._thread is not None:
            raise RuntimeError("DeviceLoader already started")
        self._stop.clear()
        self._error = None
        self._queue = queue.Queue(maxsize=self._buffer)
        self._thread = threading.Thread(target=self._worker, daemon=True)
        self._thread.start()
        return self

    def stop(self):
        self._stop.set()
        if self._thread is not None:
            # unblock a producer waiting on a full queue
            try:
                while True:
                    self._queue.get_nowait()
            except queue.Empty:
                pass
            self._thread.join(timeout=5)
            self._thread = None

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()

    def __iter__(self):
        if self._thread is None:
            self.start()
        try:
            while True:
                item = self._queue.get()
                if item is _END:
                    self._thread.join(timeout=5)
                    self._thread = None
                    if self._error is not None:
                        raise self._error
                    return
                yield item
        finally:
            # early generator close (break / exception in the consumer):
            # unblock and retire the producer so buffered device arrays
            # don't stay pinned and a later iter() starts fresh
            if self._thread is not None:
                self.stop()
