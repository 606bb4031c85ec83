"""Host-side concurrency primitives for the TPU runtime.

Reference capabilities re-founded here (not copied):
* ``MtQueue`` — blocking MPMC queue with Exit poison for shutdown
  (``include/multiverso/util/mt_queue.h:18-145``).
* ``Waiter`` — counted latch for outstanding-reply tracking
  (``include/multiverso/util/waiter.h:9-33``).
* ``ASyncBuffer`` — generic double-buffer prefetcher
  (``include/multiverso/util/async_buffer.h:10-116``).

These back the host-side dispatcher that replaces the reference's actor
threads; the device-side data path is pure XLA and never touches them.
"""

from __future__ import annotations

import threading
from collections import deque
from typing import Callable, Deque, Generic, Optional, TypeVar

T = TypeVar("T")


def next_pow2(n: int) -> int:
    """Smallest power of two >= n (n<=1 -> 1) — the shared bucket rounding
    used by table id-batches, compact PS models, and KV capacities."""
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


class MtQueue(Generic[T]):
    """Blocking multi-producer/multi-consumer queue with exit poison."""

    def __init__(self) -> None:
        self._items: Deque[T] = deque()
        self._mutex = threading.Lock()
        self._nonempty = threading.Condition(self._mutex)
        self._alive = True

    def push(self, item: T) -> None:
        with self._nonempty:
            self._items.append(item)
            self._nonempty.notify()

    def pop(self) -> Optional[T]:
        """Blocking pop; returns None once Exit() is called and queue drains."""
        with self._nonempty:
            while not self._items and self._alive:
                self._nonempty.wait()
            if self._items:
                return self._items.popleft()
            return None

    def pop_all(self) -> Optional[list]:
        """Blocking drain: wait like :meth:`pop`, then return EVERY queued
        item at once (arrival order). None once Exit() is called and the
        queue is empty — same shutdown contract as ``pop``. This is the
        dispatcher's micro-batching primitive: one wakeup hands the server
        the whole backlog so compatible Adds can fuse into a single device
        apply instead of paying per-message dispatch."""
        with self._nonempty:
            while not self._items and self._alive:
                self._nonempty.wait()
            if not self._items:
                return None
            items = list(self._items)
            self._items.clear()
            return items

    def try_pop(self) -> Optional[T]:
        with self._mutex:
            if self._items:
                return self._items.popleft()
            return None

    def front(self) -> Optional[T]:
        with self._mutex:
            return self._items[0] if self._items else None

    def empty(self) -> bool:
        with self._mutex:
            return not self._items

    def size(self) -> int:
        with self._mutex:
            return len(self._items)

    def exit(self) -> None:
        with self._nonempty:
            self._alive = False
            self._nonempty.notify_all()

    @property
    def alive(self) -> bool:
        return self._alive


class Waiter:
    """Counted latch: ``wait()`` blocks until ``notify()`` called N times."""

    def __init__(self, num_wait: int = 1) -> None:
        self._mutex = threading.Lock()
        self._cond = threading.Condition(self._mutex)
        self._num = num_wait

    def wait(self, timeout: Optional[float] = None) -> bool:
        with self._cond:
            return self._cond.wait_for(lambda: self._num <= 0, timeout)

    def notify(self) -> None:
        with self._cond:
            self._num -= 1
            if self._num <= 0:
                self._cond.notify_all()

    def reset(self, num_wait: int) -> None:
        with self._cond:
            self._num = num_wait


class AsyncBuffer(Generic[T]):
    """Double-buffer prefetcher: a background thread fills the non-current
    buffer with ``fill(buffer) -> value``; ``get()`` waits, swaps, re-prefetches.
    """

    def __init__(self, buffer0: T, buffer1: T, fill: Callable[[T], None]) -> None:
        self._buffers = [buffer0, buffer1]
        self._fill = fill
        self._current = 0
        self._ready = Waiter(1)
        self._queue: MtQueue[int] = MtQueue()
        self._error: Optional[BaseException] = None
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()
        self._queue.push(self._current)

    def _loop(self) -> None:
        while True:
            idx = self._queue.pop()
            if idx is None:
                return
            try:
                self._fill(self._buffers[idx])
            except BaseException as exc:  # surface in get(), don't die silent
                self._error = exc
                self._ready.notify()
                return
            self._ready.notify()

    def get(self) -> T:
        self._ready.wait()
        if self._error is not None:
            raise RuntimeError("AsyncBuffer fill failed") from self._error
        filled = self._current
        self._current = 1 - self._current
        self._ready.reset(1)
        self._queue.push(self._current)
        return self._buffers[filled]

    def stop(self) -> None:
        self._queue.exit()
        self._thread.join(timeout=5)


def async_upload(x):
    """Host->device transfer that ENQUEUES and returns immediately with a
    future-backed array, where ``jnp.asarray`` waits for the copy. The
    rule for every hot-path numpy upload (the difference is not measured
    on the current machine); the input must not be mutated while the
    result, or a program launched on it, can still read it: the copy is
    in flight, and a CPU client's array may be the input's own memory for
    as long as it lives (``MatrixServer._staging`` has the one place that
    writes an uploaded array again). A tuple goes up in one call (a call
    costs the chip's host 0.25 ms whatever it carries; PERF.md, PR 31)."""
    import jax
    return jax.device_put(x)
