"""Host->device input pipeline for the host-sampled (fedemnist-scale) path.

The reference streams nothing: every agent's dataset sits in one process and
local training reads it directly (src/agent.py:28, src/federated.py:68-72).
This framework's host-sampled mode (train.py: shard stacks above the
device-resident budget, e.g. fedemnist's 3383 users, src/runner.sh:34-38)
instead gathers the round's m sampled shards on host and ships them to the
mesh each round. Done synchronously, that gather + transfer sits on the
critical path between two compiled rounds.

`RoundPrefetcher` moves it off: a worker thread materializes round r+1's
(and r+2's, up to `depth`) shard stack — numpy fancy-index gather plus an
async `jax.device_put` to the agents-mesh sharding — while the TPU executes
round r. `device_put` only *enqueues* a transfer, so the copy itself overlaps
with the running round program; the consumer blocks only when compute is
faster than the pipeline can feed it. Determinism is untouched: the sampling
sequence is owned by the caller's `produce(rnd)` (seeded per round,
train.py), the prefetcher just evaluates it early.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Callable, Iterable

_SENTINEL = object()


class RoundPrefetcher:
    """Depth-bounded background producer of per-round payloads.

    produce(rnd) -> payload is called on a worker thread for each round id in
    `rounds`, in order; `get(rnd)` returns the payloads in the same order.
    A producer exception is re-raised by the next `get` call.

    Memory note: effective pipeline depth is `depth + 1` payloads resident
    at once — the queue holds `depth` plus one in the worker's hand mid-put.
    Callers sizing device memory against `--host_prefetch N` should budget
    N+2 payloads (N queued, one being dispatched, one retained for retry —
    see get()); a payload is one dispatch UNIT — a single round's [m, ...]
    stacks, or a whole [chain, m, ...] block in chained host mode
    (documented in the flag help too)."""

    # get() re-checks for a wedged worker at this period, and logs a
    # heartbeat so a hang (e.g. a stuck device_put) is attributable to
    # the pipeline rather than silently blocking the driver
    STALL_WARN_SEC = 30.0

    def __init__(self, produce: Callable, rounds: Iterable[int],
                 depth: int = 2):
        if depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {depth}")
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._err = None
        self._last = None  # (rnd, payload) most recently served — see get()
        self._thread = threading.Thread(
            target=self._worker, args=(produce, rounds), daemon=True)
        self._thread.start()

    def _put_checked(self, item) -> bool:
        """Blocking put that a racing close() can always interrupt: retries
        on a full queue until the item lands or `_stop` is set. Nothing may
        be silently dropped on queue.Full — in particular the sentinel,
        whose loss would turn the consumer's next get() into a permanent
        hang — and nothing may block forever against close() (which sets
        `_stop` and drains)."""
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.5)
                return True
            except queue.Full:
                continue
        return False

    def _worker(self, produce, rounds):
        try:
            for rnd in rounds:
                payload = produce(rnd)
                if not self._put_checked((rnd, payload)):
                    return
        except BaseException as e:  # surfaced to the consumer by get()
            self._err = e
        finally:
            self._put_checked(_SENTINEL)

    def get(self, rnd: int):
        """Blocking fetch of round `rnd`'s payload (calls must follow the
        constructor's round order). Never hangs silently: while waiting it
        logs a stall heartbeat every STALL_WARN_SEC so a wedged produce()
        (hung host gather / device_put) is attributable."""
        if self._last is not None and self._last[0] == rnd:
            # repeat request for the round just served: a supervised retry
            # (service/supervisor.py) re-dispatches the SAME unit after a
            # transient failure — popping the queue again would hand it the
            # NEXT round and trip the order check below. Costs one retained
            # payload (the +1 in the N+2 budget above), replaced on the
            # next distinct get.
            return self._last[1]
        waited = 0.0
        while True:
            try:
                item = self._q.get(timeout=self.STALL_WARN_SEC)
                break
            except queue.Empty:
                waited += self.STALL_WARN_SEC
                alive = self._thread.is_alive()
                print(f"[prefetch] stalled waiting for round {rnd} "
                      f"({waited:.0f}s; worker "
                      f"{'alive' if alive else 'DEAD'})", flush=True)
                if not alive and self._q.empty():
                    raise RuntimeError(
                        f"prefetch worker died without sentinel before "
                        f"round {rnd}") from self._err
        if item is _SENTINEL:
            if self._err is not None:
                raise RuntimeError(
                    f"prefetch worker failed before round {rnd}") \
                    from self._err
            raise RuntimeError(
                f"prefetch exhausted before round {rnd} — the driver asked "
                f"for a round outside the range it constructed")
        got, payload = item
        if got != rnd:
            raise RuntimeError(
                f"prefetch order violation: driver asked for round {rnd}, "
                f"pipeline produced round {got}")
        self._last = (got, payload)
        return payload

    def close(self) -> None:
        """Stop the worker and release anything it buffered."""
        self._stop.set()
        # keep draining until the worker exits: it may be mid-put with one
        # payload in hand, so a single drain pass can leave the queue full
        # again right before its stop-check. Bounded: give up after 10s if
        # produce() itself is stuck (daemon thread, won't block exit).
        deadline = time.monotonic() + 10.0
        while self._thread.is_alive() and time.monotonic() < deadline:
            try:
                self._q.get_nowait()
            except queue.Empty:
                pass
            self._thread.join(timeout=0.1)
        while True:
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
