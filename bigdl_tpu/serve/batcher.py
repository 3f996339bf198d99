"""Dynamic request batching: coalesce single requests into fixed shapes.

Reference gap this closes: the reference serves models either as bulk
Spark jobs (optim/Predictor.scala — whole-RDD inference) or as one
synchronous UDF call per query (example/udfpredictor/); neither shape
survives online traffic on an XLA backend, where every distinct batch
shape is a fresh compile and every single-row forward wastes the MXU.
The MLPerf TPU-pod work (arXiv:1909.09756) shows the discipline that
keeps compiled accelerators saturated: a small, fixed set of padded
batch shapes, filled as full as latency allows.

This module is the host-side half of the serving subsystem
(bigdl_tpu/serve): a bounded request queue plus the coalescing policy.

- :class:`DynamicBatcher` — concurrent producers ``submit()`` single
  samples; replica workers ``collect()`` batches.  A batch flushes when
  ``max_batch`` requests are waiting OR the oldest request has waited
  ``max_wait_s`` (the latency-vs-fill knob).  Batch sizes are drawn from
  a fixed ``buckets`` ladder (default: powers of two up to ``max_batch``)
  and padded up to the bucket, so the device only ever sees shapes it
  has already compiled (warmed up at server start).
- **Backpressure**: the queue is bounded (``queue_limit``); admission
  past the bound first sweeps queued requests whose deadline already
  expired (dead slots must shed themselves, not fresh traffic), then
  sheds the LOWEST-priority queued request if the arrival outranks it,
  and only then raises :class:`ServerOverloaded` (carrying a
  ``retry_after_s`` estimate) — typed, priority-aware rejection instead
  of unbounded latency collapse.
- **Deadlines**: a request carries an optional absolute deadline; one
  dequeued past it is shed with :class:`RequestTimeout` and never
  reaches the device (a request already executing completes normally).
- **Priorities/tenants**: requests carry ``priority`` (higher = more
  important, default 0) and an optional ``tenant`` tag; per-tenant
  token-bucket quotas live one layer up (serve/control.py), the
  shed-lowest-first policy lives here where the queue is.
- The trailing-chunk padding trick UDFPredictor (serving.py) uses for
  bulk DataFrame calls lives here too (:func:`pad_rows`,
  :func:`predict_in_fixed_batches`) — one padding implementation for
  offline UDFs and online requests.

Everything is clock-injectable and wall-clock-free under test.
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Callable, List, Optional, Sequence

import numpy as np

from ..utils import chaos, metrics_export, telemetry

__all__ = ["ServeError", "ServerOverloaded", "ServerClosed",
           "RequestTimeout", "PendingRequest", "DynamicBatcher",
           "DecodeQueue", "default_buckets", "fit_bucket", "pad_rows",
           "pad_tail", "predict_in_fixed_batches"]


class ServeError(RuntimeError):
    """Base class for typed serving rejections."""


class ServerOverloaded(ServeError):
    """Admission rejected: the bounded request queue is full (or this
    request was evicted from it for a higher-priority arrival).  The
    caller should back off / retry against another replica pool —
    queueing more would only grow everyone's latency (docs/serving.md
    decision tree).  ``retry_after_s``, when set, estimates when the
    queue will have drained (HTTP Retry-After in tools/serve_http.py)."""

    def __init__(self, message: str, retry_after_s: Optional[float] = None):
        super().__init__(message)
        self.retry_after_s = retry_after_s


class RequestTimeout(ServeError, TimeoutError):
    """The request's deadline passed while it was still queued; it was
    shed before reaching the device.  Distinct from ServerOverloaded:
    admission succeeded but service was too slow — raise the deadline or
    add replicas, not queue depth."""


class ServerClosed(ServeError):
    """submit() after shutdown began (stop() was called)."""


class PendingRequest:
    """Future-like handle for one submitted sample.

    ``result(timeout)`` blocks until a replica resolves the request and
    returns the per-sample output row, or raises the typed error the
    server recorded (RequestTimeout / ServerOverloaded at dequeue /
    ChaosFault / StallError...)."""

    __slots__ = ("payload", "enqueued", "admitted", "first_token",
                 "routing", "deadline", "tenant", "priority",
                 "version", "latency_s", "rid", "rid_owner",
                 "_event", "_result", "_error")

    def __init__(self, payload, enqueued: float,
                 deadline: Optional[float] = None,
                 tenant: Optional[str] = None, priority: int = 0,
                 rid: Optional[str] = None, rid_owner: bool = False):
        self.payload = payload
        self.enqueued = enqueued
        # the decode engine's stamps, on the clock of `enqueued`: taken
        # into a slot, and its first token sampled (None: not yet, or a
        # one-shot request, which has neither)
        self.admitted = None
        self.first_token = None
        # the experts a generated sequence's routers chose, set with the
        # result (serve/decode.py; None: a model without routed experts,
        # or a one-shot request)
        self.routing = None
        self.deadline = deadline
        self.tenant = tenant     # quota/accounting tag (control plane)
        self.priority = int(priority)  # higher = shed later
        self.version = None      # model version id that answered
        self.latency_s = None    # enqueue -> resolve
        self.rid = rid           # request flow id (X-BigDL-Request-Id)
        self.rid_owner = rid_owner  # this process minted it (it finishes)
        self._event = threading.Event()
        self._result = None
        self._error = None

    def _resolve(self, result=None, error=None, version=None,
                 now: Optional[float] = None) -> None:
        if self._event.is_set():  # first resolution wins (idempotent)
            return
        self._result = result
        self._error = error
        self.version = version
        status = type(error).__name__ if error is not None else "ok"
        if now is not None:
            self.latency_s = max(now - self.enqueued, 0.0)
            if telemetry.get_active() is not None:
                args = {"status": status}
                if self.rid is not None:
                    args["req"] = self.rid
                if self.admitted is not None:
                    # a decode request: the engine's stamps, and the
                    # lengths that turn them into time per token
                    args["queue_wait_ms"] = \
                        (self.admitted - self.enqueued) * 1e3
                    args["prompt_len"] = len(self.payload["prompt"])
                    if result is not None:
                        args["tokens"] = len(result) - args["prompt_len"]
                if self.first_token is not None:
                    args["ttft_ms"] = \
                        (self.first_token - self.enqueued) * 1e3
                telemetry.complete("serve.request", self.latency_s,
                                   cat="serve", **args)
            reg = metrics_export._REGISTRY
            if reg is not None:
                reg.observe_request(self.latency_s, status)
        if self.rid is not None:
            # the minter closes the flow; a fleet-arrived id gets a step
            # (the front owns the "f" for the whole cross-process chain)
            if self.rid_owner:
                telemetry.flow_finish(self.rid, hop="resolve",
                                      status=status)
            else:
                telemetry.flow_step(self.rid, hop="resolve", status=status)
        self._event.set()

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: Optional[float] = None):
        if not self._event.wait(timeout):
            raise TimeoutError(
                f"serve: no response within {timeout}s (request still "
                "queued or executing — not shed)")
        if self._error is not None:
            raise self._error
        return self._result


def _metrics_shed(cause: str) -> None:
    """Count one shed on the live-metrics plane (no-op when unarmed)."""
    reg = metrics_export._REGISTRY
    if reg is not None:
        reg.shed(cause)


def default_buckets(max_batch: int) -> tuple:
    """The fixed batch-shape ladder: powers of two up to ``max_batch``
    (``max_batch`` itself always included).  Small enough to warm every
    shape at startup, dense enough that a half-full flush wastes at most
    half the pad rows."""
    buckets = []
    b = 1
    while b < max_batch:
        buckets.append(b)
        b *= 2
    buckets.append(max_batch)
    return tuple(buckets)


def fit_bucket(n: int, buckets: Sequence[int]) -> Optional[int]:
    """Smallest bucket >= ``n`` from an ascending ladder, or None when
    ``n`` overflows the largest bucket.  The sequence-length counterpart
    of :meth:`DynamicBatcher.bucket_for` (which serves the batch axis and
    clamps instead — a batch can split, a sequence cannot)."""
    for b in buckets:
        if b >= n:
            return b
    return None


def pad_tail(arr: np.ndarray, length: int) -> np.ndarray:
    """Zero-pad ONLY the trailing axis up to ``length`` — the per-request
    half of :func:`pad_rows`'s ``length=`` handling, used when requests
    must land on a deterministic per-request sequence bucket BEFORE batch
    assembly (so a request's answer never depends on its batch-mates'
    lengths).  Refuses to truncate, like pad_rows."""
    arr = np.asarray(arr)
    if arr.ndim < 1:
        raise ValueError("pad_tail: needs at least a 1-D array, got "
                         f"ndim={arr.ndim}")
    have = arr.shape[-1]
    if have > length:
        raise ValueError(f"pad_tail: trailing axis {have} exceeds "
                         f"length={length} (refusing to truncate)")
    if have == length:
        return arr
    pad = [(0, 0)] * (arr.ndim - 1) + [(0, length - have)]
    return np.pad(arr, pad, mode="constant", constant_values=0)


def pad_rows(arr: np.ndarray, n: int,
             length: Optional[int] = None) -> np.ndarray:
    """Pad the batch dim up to ``n`` rows by repeating the last row — the
    fixed-shape trick that keeps jit from ever seeing a new shape (no
    per-remainder recompiles).  Shared by the online batcher and the
    offline UDF chunker.

    ``length``, when given, additionally pads the TRAILING axis up to
    ``length`` with zeros (the generative token-batch case: ragged
    prompts ride the same (bucket, page) shape ladder as fixed feature
    batches).  Rows longer than ``length`` are an error — truncation
    would silently drop tokens.  Dtype is always preserved, including
    for zero-row inputs (which still get their trailing axis resized so
    the compiled shape is honest)."""
    arr = np.asarray(arr)
    if length is not None:
        if arr.ndim < 1:
            raise ValueError("pad_rows: length= needs at least a 1-D "
                             f"array, got ndim={arr.ndim}")
        have = arr.shape[-1]
        if have > length:
            raise ValueError(f"pad_rows: trailing axis {have} exceeds "
                             f"length={length} (refusing to truncate)")
        if have < length:
            pad = [(0, 0)] * (arr.ndim - 1) + [(0, length - have)]
            arr = np.pad(arr, pad, mode="constant", constant_values=0)
    short = n - len(arr)
    if short <= 0:
        return arr
    if len(arr) == 0 and length is not None:
        # nothing to repeat: zero rows of the (resized) shape, zeros —
        # the token-batch contract (pad token 0), dtype preserved
        return np.zeros((n,) + arr.shape[1:], dtype=arr.dtype)
    return np.concatenate([arr, np.repeat(arr[-1:], short, axis=0)])


def predict_in_fixed_batches(forward: Callable, feats: np.ndarray,
                             batch_size: int) -> np.ndarray:
    """Chunk ``feats`` host-side into full ``batch_size`` batches (one XLA
    call per batch, never one giant buffer), padding the trailing chunk
    with :func:`pad_rows`, and concatenate the trimmed outputs.  The bulk
    (UDFPredictor) counterpart of the online batcher's bucket padding.
    Zero-row ``feats`` return a zero-row array without touching the
    device (the output's trailing shape is unknowable without a forward,
    so it mirrors the input's)."""
    feats = np.asarray(feats)
    if len(feats) == 0:
        return feats
    outs = []
    for i in range(0, len(feats), batch_size):
        chunk = feats[i:i + batch_size]
        outs.append(np.asarray(forward(pad_rows(chunk, batch_size)))
                    [:len(chunk)])
    return np.concatenate(outs, axis=0)


class DynamicBatcher:
    """Bounded request queue + coalescing policy (see module docstring).

    Thread contract: any number of producer threads call :meth:`submit`;
    any number of replica workers call :meth:`collect`.  ``close(drain=
    True)`` lets workers finish the queue before :meth:`collect` returns
    None; ``drain=False`` fails everything still queued with
    :class:`ServerClosed`."""

    #: wait-slice so idle workers keep heartbeating their supervisor
    #: channel (a parked worker must never read as a stalled one)
    _SLICE = 0.05

    def __init__(self, max_batch: int, max_wait_s: float,
                 queue_limit: int, buckets: Optional[Sequence[int]] = None,
                 clock=None):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        self.max_batch = int(max_batch)
        self.max_wait_s = float(max_wait_s)
        self.queue_limit = int(queue_limit)
        self.buckets = tuple(sorted(buckets)) if buckets else \
            default_buckets(self.max_batch)
        if self.buckets[-1] < self.max_batch:
            raise ValueError(f"largest bucket {self.buckets[-1]} < "
                             f"max_batch {self.max_batch}")
        self.clock = clock or time.monotonic
        self._q: collections.deque = collections.deque()
        self._cond = threading.Condition()
        self._closed = False
        self._drain = True
        # shed counters (read under the cond lock via stats())
        self.submitted = 0
        self.shed_overload = 0
        self.shed_timeout = 0
        self.shed_priority = 0      # evicted for a higher-priority arrival
        self.shed_by_priority: dict = {}  # priority class -> total sheds
        self._row_s_ema = None      # EMA service seconds/row (retry-after)

    # -- producers ------------------------------------------------------

    def _count_shed(self, req: "PendingRequest") -> None:
        # caller holds self._cond
        self.shed_by_priority[req.priority] = \
            self.shed_by_priority.get(req.priority, 0) + 1

    def _sweep_expired_locked(self, now: float) -> List["PendingRequest"]:
        """Drop queued requests whose deadline already passed (caller
        holds the lock; resolution happens outside it).  A stale queue
        must never hold ``queue_limit`` slots against fresh traffic —
        the dead requests are shed, not the arrival."""
        live, expired = collections.deque(), []
        for r in self._q:
            if r.deadline is not None and now > r.deadline:
                expired.append(r)
                self.shed_timeout += 1
                self._count_shed(r)
                _metrics_shed("timeout")
            else:
                live.append(r)
        self._q = live
        return expired

    def retry_after_s(self) -> float:
        """Seconds a rejected caller should back off: the estimated time
        to drain a full queue (EMA service rate from note_service), never
        below the coalesce window."""
        per_row = self._row_s_ema or 0.0
        return round(max(per_row * self.queue_limit, self.max_wait_s,
                         0.05), 3)

    def note_service(self, rows: int, seconds: float) -> None:
        """Feed the service-rate EMA (the server calls this after every
        successful batch) powering the retry-after estimate."""
        per = seconds / max(rows, 1)
        self._row_s_ema = per if self._row_s_ema is None else \
            0.8 * self._row_s_ema + 0.2 * per

    def service_row_seconds(self) -> Optional[float]:
        """The EMA seconds/row (None before the first served batch) —
        the service-rate signal behind retry-after and the autoscaler's
        queue-wait estimate (serve/autoscale.py)."""
        return self._row_s_ema

    def submit(self, payload, deadline: Optional[float] = None, *,
               tenant: Optional[str] = None,
               priority: int = 0,
               request_id: Optional[str] = None) -> PendingRequest:
        """Enqueue one sample; raises :class:`ServerOverloaded` when the
        bounded queue is full, :class:`ServerClosed` after shutdown.
        ``deadline`` is absolute (this batcher's clock).  When the queue
        is full, expired-deadline entries are swept first, then the
        LOWEST-priority queued request is evicted if this arrival
        strictly outranks it (shed-lowest-first under pressure).

        ``request_id`` is the distributed-tracing flow id: pass the one
        from the ``X-BigDL-Request-Id`` header when the request arrived
        through the fleet front (its flow already started there); when
        omitted and tracing is on, one is minted here and this process
        owns (finishes) the flow."""
        chaos.fire("serve.request")  # admission-path fault point
        rid, rid_owner = request_id, False
        if rid is None:
            rid = telemetry.mint_request_id()  # None when tracing is off
            rid_owner = rid is not None
        expired: List[PendingRequest] = []
        victim: Optional[PendingRequest] = None
        with self._cond:
            if self._closed:
                raise ServerClosed("serve: server is shutting down")
            if len(self._q) >= self.queue_limit:
                expired = self._sweep_expired_locked(self.clock())
            if len(self._q) >= self.queue_limit:
                # newest of the lowest-priority queued requests: it has
                # waited least, so evicting it wastes the least work
                cand = min(reversed(self._q), key=lambda r: r.priority)
                if cand.priority < int(priority):
                    self._q.remove(cand)
                    victim = cand
                    self.shed_priority += 1
                    self._count_shed(cand)
                    _metrics_shed("priority")
                else:
                    self.shed_overload += 1
                    self.shed_by_priority[int(priority)] = \
                        self.shed_by_priority.get(int(priority), 0) + 1
                    _metrics_shed("overloaded")
                    retry = self.retry_after_s()
                    raise ServerOverloaded(
                        f"serve: request queue full ({self.queue_limit} "
                        f"waiting, none below priority {int(priority)}) "
                        f"— shedding at admission; retry in {retry}s",
                        retry_after_s=retry)
            req = PendingRequest(payload, self.clock(), deadline,
                                 tenant=tenant, priority=priority,
                                 rid=rid, rid_owner=rid_owner)
            self._q.append(req)
            self.submitted += 1
            depth = len(self._q)
            self._cond.notify_all()
        if rid is not None:
            if rid_owner:
                telemetry.flow_start(rid, hop="queue.enqueue", depth=depth)
            else:
                telemetry.flow_step(rid, hop="queue.enqueue", depth=depth)
        now = self.clock()
        for r in expired:
            r._resolve(error=RequestTimeout(
                f"serve: deadline expired after {now - r.enqueued:.3f}s "
                "in queue (swept at admission)"), now=now)
        if victim is not None:
            victim._resolve(error=ServerOverloaded(
                f"serve: shed from a full queue for a priority-"
                f"{int(priority)} arrival (this request: priority "
                f"{victim.priority}); retry in {self.retry_after_s()}s",
                retry_after_s=self.retry_after_s()), now=now)
        telemetry.counter("serve", queue_depth=depth)
        return req

    def depth(self) -> int:
        with self._cond:
            return len(self._q)

    # -- workers --------------------------------------------------------

    def collect(self, heartbeat: Optional[Callable] = None,
                stop_when: Optional[Callable] = None
                ) -> Optional[List[PendingRequest]]:
        """Block until a batch is ready, the coalesce window expires, or
        shutdown.  Returns up to ``max_batch`` live requests (may be []
        when every dequeued request had expired — the caller just loops),
        or None when the batcher is closed and (if draining) empty.
        ``heartbeat`` is called on every wait slice so the worker's
        supervisor channel stays live while parked.  ``stop_when`` (a
        predicate checked per wait slice) lets a caller retire a worker
        parked on an EMPTY queue without closing the batcher — the pool
        shrink path (serve/autoscale.py): a condemned replica must not
        stay parked until the next request arrives just to notice its
        condemnation."""
        with self._cond:
            while not self._q:
                if self._closed:
                    return None
                if stop_when is not None and stop_when():
                    return None
                self._cond.wait(self._SLICE)
                if heartbeat is not None:
                    heartbeat()
            # coalesce: from the OLDEST waiting request's enqueue time,
            # hold the flush up to max_wait_s hoping to fill the batch —
            # the configurable latency-for-fill trade
            flush_at = self._q[0].enqueued + self.max_wait_s
            while len(self._q) < self.max_batch and not self._closed:
                remaining = flush_at - self.clock()
                if remaining <= 0:
                    break
                self._cond.wait(min(remaining, self._SLICE))
                if heartbeat is not None:
                    heartbeat()
            reqs = [self._q.popleft()
                    for _ in range(min(len(self._q), self.max_batch))]
        # deadline shedding happens at dequeue, outside the lock: an
        # expired request never reaches the device
        now = self.clock()
        live = []
        for r in reqs:
            if r.deadline is not None and now > r.deadline:
                with self._cond:
                    self.shed_timeout += 1
                    self._count_shed(r)
                _metrics_shed("timeout")
                r._resolve(error=RequestTimeout(
                    f"serve: deadline exceeded after "
                    f"{now - r.enqueued:.3f}s in queue"), now=now)
            else:
                live.append(r)
        return live

    def bucket_for(self, n: int) -> int:
        """Smallest bucket >= n (n is capped at max_batch by collect)."""
        for b in self.buckets:
            if b >= n:
                return b
        return self.buckets[-1]

    def requeue(self, reqs: Sequence["PendingRequest"]) -> None:
        """Hand collected-but-unserved requests back to the queue HEAD in
        their original order — a condemned/dying replica (serve/control
        teardown, the ``serve.replica`` exit drill) must lose zero
        accepted requests.  After a no-drain close there is nobody left
        to serve them: they fail typed instead."""
        reqs = [r for r in reqs if not r.done()]
        if not reqs:
            return
        stranded = None
        with self._cond:
            if self._closed and not self._drain:
                stranded = reqs
            else:
                for r in reversed(reqs):
                    self._q.appendleft(r)
                self._cond.notify_all()
        if stranded:
            now = self.clock()
            for r in stranded:
                r._resolve(error=ServerClosed(
                    "serve: server stopped before this request ran"),
                    now=now)

    def fail_pending(self, error: Optional[Exception] = None) -> int:
        """Resolve everything still queued with a typed error (default
        :class:`ServerClosed`) and return how many there were — the final
        shutdown sweep for queues nobody is left to drain (dead replica
        pool, drain interrupted), so no caller ever blocks on
        ``result()`` forever."""
        with self._cond:
            pending = [r for r in self._q if not r.done()]
            self._q.clear()
        now = self.clock()
        err = error if error is not None else ServerClosed(
            "serve: server stopped before this request ran")
        for r in pending:
            r._resolve(error=err, now=now)
        return len(pending)

    # -- shutdown -------------------------------------------------------

    def close(self, drain: bool = True) -> None:
        """Stop admissions.  drain=True lets workers finish the queue;
        drain=False fails everything still queued with ServerClosed."""
        with self._cond:
            self._closed = True
            self._drain = drain
            pending = []
            if not drain:
                while self._q:
                    pending.append(self._q.popleft())
            self._cond.notify_all()
        now = self.clock()
        for r in pending:
            r._resolve(error=ServerClosed(
                "serve: server stopped before this request ran"), now=now)

    @property
    def closed(self) -> bool:
        return self._closed

    def stats(self) -> dict:
        with self._cond:
            return {"queue_depth": len(self._q),
                    "submitted": self.submitted,
                    "shed_overload": self.shed_overload,
                    "shed_timeout": self.shed_timeout,
                    "shed_priority": self.shed_priority,
                    "shed_by_priority": {str(k): v for k, v in
                                         sorted(self.shed_by_priority
                                                .items())}}


class DecodeQueue(DynamicBatcher):
    """Per-SEQUENCE admission queue for the generative decode engine
    (serve/decode.py).

    Same bounded queue, deadlines, priority eviction and shed policy as
    :class:`DynamicBatcher` — a queued item is one *sequence* (prompt +
    generation budget), not one feature row, and the consumer is the
    engine's persistent step loop rather than a replica pool:

    - :meth:`take` pops up to ``n`` live sequences WITHOUT blocking or
      coalescing — the step loop admits into whatever slots just freed
      and must never park while other slots are still decoding.
    - :meth:`note_service` is fed (tokens, seconds), so the EMA learns
      seconds/TOKEN; ``retry_after_s`` therefore scales with the queue's
      total outstanding token budget, not its request count.
    """

    def __init__(self, queue_limit: int, max_wait_s: float = 0.0,
                 clock=None):
        # max_batch/buckets are meaningless per-sequence: slots and the
        # (slots, cache-page) ladder live in the engine
        super().__init__(max_batch=1, max_wait_s=max_wait_s,
                         queue_limit=queue_limit, buckets=(1,),
                         clock=clock)
        self._pending_tokens = 0  # queued generation budget (retry-after)

    def submit(self, payload, deadline: Optional[float] = None, *,
               tenant: Optional[str] = None,
               priority: int = 0,
               request_id: Optional[str] = None) -> PendingRequest:
        req = super().submit(payload, deadline, tenant=tenant,
                             priority=priority, request_id=request_id)
        with self._cond:
            self._pending_tokens += int(payload.get("max_tokens", 1)) \
                if isinstance(payload, dict) else 1
        return req

    def retry_after_s(self) -> float:
        """Back-off estimate for a rejected sequence: EMA seconds/token
        times the *queued token budget* (a queue of 8 sequences at 256
        tokens each is 2048 steps of work, not 8)."""
        per_tok = self._row_s_ema or 0.0
        return round(max(per_tok * max(self._pending_tokens, 1),
                         self.max_wait_s, 0.05), 3)

    def take(self, n: int) -> List[PendingRequest]:
        """Pop up to ``n`` live sequences, non-blocking.  Expired
        deadlines shed at dequeue exactly like :meth:`collect` (a
        sequence whose time-to-last-token deadline already passed must
        never occupy a slot).  Returns [] when the queue is empty."""
        if n <= 0:
            return []
        with self._cond:
            reqs = [self._q.popleft()
                    for _ in range(min(len(self._q), n))]
            for r in reqs:
                if isinstance(r.payload, dict):
                    self._pending_tokens = max(
                        0, self._pending_tokens
                        - int(r.payload.get("max_tokens", 1)))
        now = self.clock()
        live = []
        for r in reqs:
            if r.deadline is not None and now > r.deadline:
                with self._cond:
                    self.shed_timeout += 1
                    self._count_shed(r)
                _metrics_shed("timeout")
                r._resolve(error=RequestTimeout(
                    f"serve: deadline exceeded after "
                    f"{now - r.enqueued:.3f}s in queue (decode "
                    "admission)"), now=now)
            else:
                live.append(r)
        return live

    def wait_for_work(self, timeout: float) -> bool:
        """Park the step loop (briefly) until a sequence is queued or the
        queue closes.  Returns True when there may be work.  Each sleep is
        one ``decode.idle`` span (the engine had nothing to do: the
        device's idle time there is the traffic's, not the host's); a call
        that returns at once leaves none."""
        with self._cond:
            if self._q or self._closed:
                return True
            with telemetry.span("decode.idle", cat="serve"):
                self._cond.wait(timeout)
            return bool(self._q) or self._closed
