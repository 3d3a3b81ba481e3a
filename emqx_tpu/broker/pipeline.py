"""PublishPipeline — the {active,N}-style coalescing stage that puts the
device router on the LIVE serving path.

The reference's hot loop is one trie walk per message inside the
publishing client's process (emqx_broker.erl:218-232 via
emqx_connection.erl:132's ``{active,N}`` socket batching).  The TPU-era
shape inverts it: connections *submit* publishes into a queue; a single
flusher drains whatever accumulated — while the previous device step was
in flight — into one ``Broker.publish_batch`` kernel launch, then fans
the merged deliveries out through the CM.  Batch assembly overlaps
device execution exactly like ``{active,N}`` overlaps socket reads with
dispatch (SURVEY.md §2.5-6 pipeline parallelism).

Correctness notes:

- per-publisher ordering: FIFO queue + in-order batch results ⇒ a
  client's publishes deliver in submission order (the reference's
  per-connection ordering guarantee);
- acks don't wait: QoS1/2 acks depend only on local session state, not
  on delivery fan-out (same as the reference, where PUBACK is sent as
  soon as ``emqx_broker:publish/1`` returns and the actual subscriber
  sends are async process messages);
- hooks (`message.publish` fold: rules, retainer, delayed...) run at
  flush time inside ``publish_batch`` — same hook surface, same order.
"""

from __future__ import annotations

import asyncio
import logging
import threading
import time
from collections import deque
from typing import Optional

from emqx_tpu.core.message import Message

log = logging.getLogger("emqx_tpu.pipeline")


class PublishPipeline:
    """Thread-safe publish coalescer over ``Broker.publish_batch``.

    Servers wire ``submit`` as the channels' ``publish_sink``; the
    asyncio host runs ``flusher()`` as a background task, the native
    host calls ``flush()`` after each poll step.
    """

    def __init__(self, broker, cm, max_batch: int = 512) -> None:
        self.broker = broker
        self.cm = cm
        self.max_batch = max_batch
        # latency policy (SURVEY §7 hard part (b), VERDICT r3 #3): a
        # batch below the knee answers from the host oracle in
        # microseconds instead of paying the device round trip.
        #   min_device_batch >= 0: fixed threshold (config
        #   router.device.min_batch); -1 (default): adaptive — the knee
        #   is device_RTT / host_cost from running EMAs of both, so a
        #   high-RTT device floors small batches onto the host while a
        #   sub-ms RTT keeps the device path for batch >= ~100.
        self.min_device_batch = -1
        self._rtt_ema = 5e-3       # device round trip per batch (s)
        self._host_cost_ema = 6e-6 # host-oracle walk per message (s)
        self.host_batches = 0      # batches that took the bypass
        self._since_device = 0     # bypasses since the last device batch
        # in-flight launch depth: with a fixed device RTT
        # the service rate is depth x max_batch / RTT — depth, not batch
        # size, is the loaded-latency lever. Config:
        # router.device.pipeline_depth.
        self.depth = 4
        # sojourn spill: a batch whose OLDEST message already waited
        # past the deadline answers from the host oracle (µs) instead
        # of joining the device queue — bounding loaded p99 near the
        # deadline. <0 = adaptive (3 x RTT EMA, floored at 30 ms).
        self.spill_ms = -1.0
        self.spilled_batches = 0
        self._q: deque[Message] = deque()
        self._lock = threading.Lock()
        # serializes concurrent consumers (the flusher task's to_thread
        # flush vs. stop()'s final drain): batches must never interleave
        # or race the model's donated device buffers
        self._consumer_lock = threading.Lock()
        self._flusher_task: Optional[asyncio.Task] = None
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._wake: Optional[asyncio.Event] = None
        self.batches = 0          # flush count (≈ kernel launches)
        self.published = 0

    # -- producer side ------------------------------------------------------

    def submit(self, msg: Message) -> None:
        with self._lock:
            self._q.append(msg)
        wake, loop = self._wake, self._loop
        if wake is not None and loop is not None:
            try:
                if asyncio.get_running_loop() is loop:
                    wake.set()
                    return
            except RuntimeError:
                pass
            try:
                loop.call_soon_threadsafe(wake.set)
            except RuntimeError:
                pass          # loop closed; stop()'s final flush drains

    def pending(self) -> int:
        with self._lock:
            return len(self._q)

    # -- consumer side ------------------------------------------------------

    def spill_deadline_ms(self) -> Optional[float]:
        """Queue-sojourn bound before a batch spills to the host
        oracle; adaptive default tracks the measured device RTT.
        ``None`` disables the implicit spill: a config that PINS the
        knee to 0 (force-kernel mode — benches and kernel-path tests
        that need every batch on the device) must not be silently
        diverted under load; an explicit spill_ms still applies."""
        if self.spill_ms >= 0:
            return self.spill_ms
        if self.min_device_batch == 0:
            return None
        return max(3e3 * self._rtt_ema, 30.0)

    def flush(self) -> int:
        """Drain the queue in ≤max_batch launches; returns messages
        flushed.  Safe from multiple consumer threads (serialized).

        Pipelined to ``depth`` in-flight launches: batches k+1..k+depth
        have their hooks+tokenize+launch run BEFORE batch k's results
        are collected, so the device round trip overlaps both host work
        and the OTHER in-flight round trips — service rate ≈ depth ×
        max_batch / RTT (SURVEY §2.5-6). Collection stays in submission order,
        preserving per-publisher delivery order, and batches whose head
        message out-waited the spill deadline answer from the host
        oracle so loaded p99 stays bounded."""
        total = 0
        with self._consumer_lock:
            inflight: deque = deque()             # (batch, broker token)
            try:
                while True:
                    batch = []
                    if len(inflight) < max(1, self.depth):
                        with self._lock:
                            batch = [
                                self._q.popleft()
                                for _ in range(min(len(self._q),
                                                   self.max_batch))]
                    if batch:
                        # small batch: the host oracle answers in µs;
                        # the device RTT would dominate (latency knee)
                        bypass = len(batch) < self.device_knee()
                        if (bypass and self.min_device_batch < 0
                                and len(batch) >= 8
                                and self._since_device >= 64):
                            # adaptive mode must not ratchet one-way: a
                            # stale RTT prior that saturates the knee
                            # would otherwise never be re-measured. A
                            # periodic probe batch rides the device to
                            # refresh the EMA.
                            bypass = False
                        if not bypass:
                            deadline = self.spill_deadline_ms()
                            sojourn = time.time() * 1e3 - batch[0].timestamp
                            if deadline is not None and sojourn > deadline:
                                # the device queue is saturated: this
                                # batch's wait already ate the latency
                                # budget — the oracle answers now
                                bypass = True
                                self.spilled_batches += 1
                        if bypass:
                            self.host_batches += 1
                            self._since_device += 1
                        else:
                            self._since_device = 0
                        token = self.broker.publish_batch_submit(
                            batch, force_host=bypass)
                        if token is not None:
                            inflight.append((batch, token))
                    if inflight and (not batch
                                     or len(inflight) >= max(1, self.depth)):
                        pbatch, ptoken = inflight.popleft()
                        # counters first: an observer that saw a
                        # delivery must also see it counted (dispatch
                        # wakes sockets before this thread would
                        # otherwise increment)
                        self.batches += 1
                        total += len(pbatch)
                        self.published += len(pbatch)
                        self._collect_dispatch(ptoken)
                    if not batch and not inflight:
                        return total
            finally:
                # a raising submit/collect must not strand the OTHER,
                # already-submitted (and already-acked) batches — their
                # hooks ran and their device steps succeeded; deliver
                # them in order
                while inflight:
                    pbatch, ptoken = inflight.popleft()
                    self.batches += 1
                    self.published += len(pbatch)
                    try:
                        self._collect_dispatch(ptoken)
                    except Exception:       # noqa: BLE001
                        log.exception(
                            "pending batch collect failed; batch dropped")

    def device_knee(self) -> int:
        """Batch size below which the host oracle beats the device.
        Fixed by config (router.device.min_batch >= 0) or adaptive:
        knee = device-RTT / host-cost-per-message, both running EMAs
        measured at collect time. At a ~70 ms RTT the knee saturates at
        max_batch (host path serves latency, device path serves
        saturated full batches); at a sub-ms RTT it sits around 10²."""
        if self.broker.model is None:
            return 0                    # no device path configured
        if self.min_device_batch >= 0:
            return self.min_device_batch
        return min(self.max_batch,
                   max(1, int(self._rtt_ema
                              / max(self._host_cost_ema, 1e-9))))

    def _collect_dispatch(self, token) -> None:
        t0 = time.perf_counter()
        results = self.broker.publish_batch_collect(token)
        dt = time.perf_counter() - t0
        live = token[1]
        if not live:
            pass          # hook-dropped batch: nothing was routed, so
        elif token[4] is None:          # no cost signal — don't poison
            # host-oracle batch: normalize by messages actually routed
            per_msg = dt / len(live)
            self._host_cost_ema += 0.2 * (per_msg - self._host_cost_ema)
        else:                           # device batch: effective blocked
            self._rtt_ema += 0.2 * (dt - self._rtt_ema)  # time at collect
        merged: dict[str, list] = {}
        for d in results:
            for sid, items in d.items():
                merged.setdefault(sid, []).extend(items)
        if merged:
            self.cm.dispatch(merged)

    def ensure_flusher(self) -> asyncio.Task:
        """Start (or adopt) the ONE flusher task for the running loop.
        The pipeline owns the task — several listeners sharing one app
        (tcp + ws) must not each spawn/cancel their own flusher, or one
        listener's shutdown would orphan the others' deliveries."""
        loop = asyncio.get_running_loop()
        if (self._flusher_task is None or self._flusher_task.done()
                or self._loop is not loop):
            self._loop = loop
            self._wake = asyncio.Event()
            self._flusher_task = loop.create_task(self.flusher())
        return self._flusher_task

    async def flusher(self) -> None:
        """Asyncio consumer: wake on submit, drain off-loop (the device
        step blocks a thread, not the accept loop; submissions landing
        during a flush coalesce into the next batch — the overlap).
        A failing batch is logged and dropped — one poisoned message (a
        raising hook, a device error) must not kill delivery forever."""
        if self._loop is None:
            self._loop = asyncio.get_running_loop()
        if self._wake is None:
            self._wake = asyncio.Event()
        wake = self._wake
        while True:
            await wake.wait()
            wake.clear()
            try:
                await asyncio.to_thread(self.flush)
            except asyncio.CancelledError:
                raise
            except Exception:
                log.exception("publish flush failed; batch dropped")
