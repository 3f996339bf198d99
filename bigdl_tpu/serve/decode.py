"""Continuous-batching generative decode serving.

Everything serve/ shipped before this module is one-shot forward: a
request is one feature row, a batch is one device call, done.  Real
serving traffic is dominated by autoregressive DECODE — and the offline
KV-cache decoder (models/decode.py ``cached_generate``) never met
``InferenceServer``.  This module closes that gap with the classic
continuous-batching design (the step BigDL 2.0's Cluster Serving never
took; PAPERS.md):

- :class:`DecodeEngine` runs a **persistent decode step loop** over a
  fixed-slot in-flight batch.  Every loop tick decodes ALL active slots
  in ONE kernel call; a sequence that exhausts its token budget frees
  its slot **with the call of its last step** instead of holding the
  batch hostage (run-to-completion static batching wastes device steps
  on finished rows — the throughput gap tools/decode_smoke.py gates, not
  asserts).
- **The loop runs one tick ahead of its reads.**  The slots' last tokens
  live on the device as one ``int32[slots]`` vector: a prefill takes it
  and returns it with its group's rows set to their prompts' first
  tokens, a step takes it as its input tokens and leaves the next one.  A tick
  calls, then reads: free slots are known from counts (``_Seq.called``),
  requests are taken, their prefills called, the step called, and only
  then one ``jax.device_get`` brings down what the calls of the tick
  BEFORE left (the step's tokens, the admissions' first tokens, the
  experts' counts and routing of both); first tokens are stamped and
  finished requests answered there.  The device so holds its next
  programs whenever it ends one, and a request is still answered as soon
  as its last step ends (the host is by then blocked in that step's
  fetch).  ``stats()["steps_ahead"]`` counts the steps called with a
  call before them unread.  What cannot run ahead reads first, decided
  by what a tick holds and by no setting: a slot whose request samples
  has its next token on the host alone, so a tick that holds one reads
  before it calls and the step takes that row's token from the host
  (``feed``); an idle re-page, ``stop()`` and a tick with nothing to
  call read everything that waits.  An EOS is seen a call late: the slot
  computes one row too many (two after a prompt whose first token is its
  EOS), counted nowhere a request can see
  (``tokens_out``, ``tokens_device_sampled``, the result and its routing
  are those of the tokens received; the experts' counts alone take it
  in), and what it wrote lies past the next prompt's reach or under its
  prefill, like an idle row's.
- **Prefill and decode are separate jitted executables** with separate
  compile cards and AOT cache entries, keyed like the
  ``_ShardedForward`` buckets (module fingerprint + base fingerprint +
  shape dims through utils/aot.get_or_compile).  Prefill admits a GROUP
  of new sequences into free KV-cache slots in ONE pass: the padded
  prompt buckets go through the model as ``[rows, bucket, E]`` (every
  weight read once a group, every product matrix-matrix), each layer
  with decode state writes all positions into the group's slots by one
  in-place scatter a leaf, and past the last such layer only each
  prompt's last real position goes on to the head (models/decode
  ``_prefill``; one compile per (rows, prompt-bucket, slots,
  cache-page), the slots and the prompts' lengths traced).  Both
  programs are one walk (models/decode ``_Walk``) over
  what each layer declares (``Module.decode_state``, ``decode_prefill``,
  ``decode_step``): ``MultiHeadAttention`` keeps ``{k, v}`` a key-value
  head, ``LatentAttention`` a latent and one rotary key for all heads,
  ``Mamba2Mixer`` a recurrent state and a convolution's last inputs, of
  fixed size whatever the length (a prefill writes such a row whole, a
  growing cache carries it over; models/decode.py), and the engine knows
  none of them.  ``cached_generate`` keeps its position-by-position walk
  and shares no prefill code with the engine:
  it is the oracle the engine's greedy tokens are held to, token for
  token, by test.
- **The greedy token is chosen where the log-probabilities lie.**  Both
  programs compute, beside the logits, the index of each row's largest
  entry (the first among equals, ``np.argmax``'s rule on the same
  bfloat16 values) and return the slots' ``int32[slots]`` vector with
  it: every row from the step, the admitted slots' from the prefill.
  The host fetches those and what the expert layers report, and
  the ``[slots, vocabulary]`` array stays on the device.  What a request
  says decides its row's way, nothing else: ``temperature`` 0 takes the
  device's token; ``temperature`` above 0 has its row fetched
  (``_LogitRow``, which fetches itself when turned into an array) and
  goes through ``sample_next`` on the host with the request's own seed.
- The bucket ladder extends to **(batch-slots, cache-page)** pages:
  cache length is allocated in power-of-2 multiples of
  ``BIGDL_TPU_DECODE_PAGE`` (models/decode.init_kv_cache buffers), so a
  17-token prompt neither compiles nor pays HBM for ``max_len``.  The
  cache grows to the next page (each leaf along its own length axis)
  when a longer sequence is admitted and shrinks back when the engine
  drains idle.  Under a canonical layout mesh each leaf carries its
  declared role (parallel/layout.py ``kv_cache``: slots over data x
  fsdp, heads over tp; ``latent_cache``: slots alone), so tp-sharded
  models serve decode through the existing mesh machinery unchanged.
- **Admission in groups.**  A pass's requests enter in the order they
  were taken, and those of one prompt bucket share prefill calls: n
  prompts pay the weights once, where a call a prompt streams them n
  times.  How many rows a call may have is read off declared shapes
  (``_prefill_programs``), as one budget of positions a call (rows x
  bucket; ``_call_positions``): as many as keep the call's multiply-adds
  (a position's, counted from the one-row program's own trace,
  utils/flops) from outweighing the weight bytes it streams, and no more
  than the longest single prompt the engine must take anyway needs beside
  the cache; and no group is wider than half the requests a backlogged
  pass may wait for.  A bucket has its one-row program and, where its
  widest group has four rows or more, one program of that many (a power
  of two; every program costs seconds of every start); a smaller group
  fills that program up with rows that write nothing and are counted
  nowhere; both are compiled when the bucket first is, so a later burst
  compiles nothing.  When to wait is read off
  the queue (``_take_now``): while no more requests wait than slots are
  free, whatever arrived goes at once (a lone request on an idle engine
  is called in the pass that takes it); under a backlog freed slots are
  held until the pass can take as many requests as keep ``_HELD_SHARE``
  of the slots free on average (``_take_cap``: a take spreads over the
  buckets in use, and a fuller take makes fuller groups), and no longer
  than the taken slots' own remaining counts said that would need.  No setting
  chooses any of it.
- Admission rides :class:`~bigdl_tpu.serve.batcher.DecodeQueue`:
  bounded queue, per-sequence deadline (= time-to-LAST-token), priority
  eviction and tenant quotas all apply per-sequence; ``note_service``
  learns seconds/token so ``retry_after_s`` scales with the queued
  token budget.
- Telemetry: a working pass of the loop is one ``decode.tick`` span
  (``active``: slots carried over from the pass before, ``admitted``) with
  ``decode.admit`` (one a prefill call: ``rows`` requests of one
  ``bucket``, ``prompt_len`` their real tokens), ``decode.step`` and
  ``decode.sample`` inside it; in the first two, ``decode.call`` is the
  host's part up to the executable's return; ``decode.fetch``, in
  ``decode.step`` after its call (in the tick itself where a pass calls
  no step), is the one ``jax.device_get``: the host blocked on the device
  and the transfer, for the calls of the pass before (``tick``: that
  pass's number; ``calls``, ``bytes``).  A
  pass with nothing to do sleeps in ``decode.idle`` (serve/batcher.py).
  Every active slot gets one token a tick, so the gaps a caller sees
  between tokens are the periods of consecutive ticks; a request's flow
  has four events (``queue.enqueue``, ``decode.admit``,
  ``decode.first_token``, ``resolve``) whatever its length, and
  ``serve.request`` carries ``queue_wait_ms``, ``ttft_ms``, ``prompt_len``
  and ``tokens``.  The ``serve.decode`` counter track emits tokens/s,
  active-slot fill, prefill-vs-decode step fractions (``prefill_steps``
  counts device calls of the prefill, ``prefill_rows`` the requests
  they admitted: ``prefill_group`` is rows a call), the share of the
  slots' steps kept free for a fuller group (``held_share``:
  ``slot_steps_held`` over slots x steps), the share of the
  prefills' positions that were padding (``prefill_pad_frac``), cache
  bytes/slot and the part of them that is of fixed size
  (``state_bytes_per_slot``; ``state_bytes_fixed`` is that part over all
  slots, and ``state_bytes_per_position`` what a slot holds of each
  position, so the two kinds of cache read side by side; ``mean_position``
  is how far the last step's rows stood, + 1: what an attention layer that
  grows reads of each, where ``slot_positions`` in ``stats()`` sums it over
  rows and steps), and how often the device's token was taken
  (``tokens_device_sampled``) against rows of log-probabilities brought
  to the host for requests that sample (``logit_rows_fetched``: 0 under
  greedy traffic), and ``ran_ahead`` (1 where the pass's step was called
  with a call before it unread; its mean is the share the ``decode:``
  line prints) — promoted to a ``decode:`` trace_report
  section like ``aot``/``autoscale`` (utils/telemetry.phase_breakdown).  A
  model with routed experts that count their tokens (parallel/expert.GatedMoE)
  returns one small count vector beside the tokens of every call:
  ``expert_tokens`` (choices that went to experts held here),
  ``expert_tokens_elsewhere`` and ``expert_tokens_max`` (the busiest held
  expert's), so load balance reads as max over mean.
- Routing: a model with routed experts also returns, beside the counts,
  the experts every position's router chose (a few integers a token), and
  a finished request carries its own as ``PendingRequest.routing``: int32
  ``[expert layers, positions, k]`` for positions ``0 .. len(result) - 2``
  (each position whose output chose the next token or fed the state), -1
  where a prefill computed nothing (past the last layer that keeps state
  only a prompt's last position is computed).  Routing is discrete: who
  holds served tokens against another computation of the model has to
  give it the choices that were made.  None for a model without routed
  experts.
- Chaos: ``serve.decode@<slot>`` fires once per tick for every slot
  that participates (prefill or decode).  A faulted slot fails ITS
  sequence typed (:class:`SlotFault`/ChaosFault), frees the slot (rows
  of it that wait unread are dropped), and the other slots keep decoding
  with zero loss.

Config knobs (utils/config, all overridable per-engine):

=============================  =========  ================================
env var                        default    meaning
=============================  =========  ================================
BIGDL_TPU_DECODE_SLOTS         4          fixed in-flight batch slots
BIGDL_TPU_DECODE_PAGE          128        cache-page quantum (tokens);
                                          cache length is page * 2^k
BIGDL_TPU_DECODE_MAX_LEN       0          cache-length cap; 0 = the
                                          model's positional max_len
BIGDL_TPU_DECODE_QUEUE_LIMIT   64         bounded admission queue
BIGDL_TPU_DECODE_DEADLINE_MS   0          default time-to-last-token
                                          deadline; 0 = none
BIGDL_TPU_DECODE_ADMISSION     continuous 'continuous' (join per tick) or
                                          'batch' (run-to-completion —
                                          the baseline decode_smoke
                                          measures against)
BIGDL_TPU_DECODE_MIN_STEP_MS   0          per-tick pacing floor (drill /
                                          smoke determinism lever)
=============================  =========  ================================
"""

from __future__ import annotations

import threading
import time
from functools import partial
from typing import List, Optional

import numpy as np
import jax
import jax.numpy as jnp

from ..models import decode as kv
from ..models.transformer_lm import PositionalEmbedding, sample_next
from ..utils import aot as aot_mod
from ..utils import flops as flops_mod
from ..utils import chaos, config, hlostats, metrics_export, telemetry
from .batcher import DecodeQueue, PendingRequest, ServeError
from .control import TenantQuotas

__all__ = ["DecodeEngine", "SlotFault", "page_ladder"]

_UNSET = object()


class SlotFault(ServeError):
    """A decode slot faulted mid-generation (the ``serve.decode@<slot>``
    chaos drill, or a per-sequence error): the sequence fails typed, the
    slot frees the same tick, the other slots keep decoding."""


def page_ladder(page: int, max_len: int) -> tuple:
    """The cache-length ladder: power-of-2 multiples of ``page`` capped
    at ``max_len`` (``max_len`` itself always included) — the cache-page
    analogue of batcher.default_buckets."""
    if page < 1:
        raise ValueError(f"page must be >= 1, got {page}")
    sizes = []
    c = int(page)
    while c < max_len:
        sizes.append(c)
        c *= 2
    sizes.append(int(max_len))
    return tuple(sizes)


def _prompt_bucket(t0: int) -> int:
    """Power-of-2 prompt padding bucket (floor 8) — one prefill
    executable per bucket, not per prompt length."""
    b = 8
    while b < t0:
        b *= 2
    return b


#: multiply-adds a call may spend for every byte of weights it streams
#: before its arithmetic outweighs them: up to here part of a row's work
#: hides under the weights' streaming, past it a call's time grows by a
#: row's whole work and a wider group saves little.  Read on the chip by
#: (rows, bucket) in three models (tools/prefill_rows.py; PERF.md, PR 42:
#: a call's time follows its positions, whatever their split into rows,
#: with a knee at 100 and 128 where one is seen; a model whose one prompt
#: counts 226 does not group).
_FLOPS_PER_WEIGHT_BYTE = 128.0

#: the share of the slots a backlogged engine may keep free, on average,
#: while it waits for a pass's take to fill its groups
_HELD_SHARE = 1.0 / 32

#: the narrowest group that gets a program of its own.  Not read off the
#: chip's table of calls but off what a program costs to bring up: seconds
#: of every start, cold or warm (trace, lower, load: 3.3 s warm where a
#: start is 44 s, PERF.md, PR 42), for a pair that saves at most half a
#: call's weights
_MIN_GROUP_ROWS = 4


class _Seq:
    """Host-side state of one in-flight sequence (one slot).  ``called``
    counts the tokens asked of the device (its prefill and every step that
    carried its row), ``emitted`` those the host has read: the loop runs a
    call ahead of its reads, so the first says when the slot is free and
    the second when the request is answered."""

    __slots__ = ("req", "slot", "buf", "t0", "called", "emitted",
                 "max_tokens", "eos", "temperature", "top_k", "rng",
                 "routed", "over")

    def __init__(self, req: PendingRequest, slot: int, prompt: np.ndarray,
                 max_tokens: int, eos, temperature: float, top_k: int,
                 rng):
        self.req = req
        self.slot = slot
        self.t0 = len(prompt)
        self.buf = np.zeros(self.t0 + max_tokens, np.int32)
        self.buf[: self.t0] = prompt
        self.called = 0
        self.emitted = 0
        self.max_tokens = max_tokens
        self.eos = eos
        self.temperature = temperature
        self.top_k = top_k
        self.rng = rng
        # the experts each position's routers chose (PendingRequest.routing)
        self.routed: Optional[np.ndarray] = None
        self.over = False        # answered, with a row or an error


class _Call:
    """One device call whose results the host has not read: the pass that
    made it, its program, the rows it computed as ``(sequence, position)``
    (a prefill's rows have no position: they are its group's, in the
    program's order), and what it left on the device: the
    log-probabilities, the ``int32[slots]`` tokens after it and what the
    expert layers report."""

    __slots__ = ("tick", "program", "rows", "logits", "tokens", "report")

    def __init__(self, tick: int, program: str, rows, logits, tokens,
                 report):
        self.tick, self.program, self.rows = tick, program, rows
        self.logits, self.tokens, self.report = logits, tokens, report


def _with_tokens(logits, caches, report):
    """What ``models/decode`` ``_slot_step`` or ``_prefill`` returns, with
    the index of each row's largest entry put beside the logits, the first
    among equals: ``np.argmax``'s rule on the same values, which is part of
    the result (bfloat16 log-probabilities tie often).  The barrier holds
    the compiler to the values that leave the program: fused into the
    log-softmax it would compare them as they are before they are rounded
    to bfloat16, where ties are none."""
    logits = jax.lax.optimization_barrier(logits)
    tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return logits, tokens, caches, report


class _LogitRow:
    """One row of log-probabilities as the program left it on the device,
    and ``token``, the index of its largest entry as the same program chose
    it.  ``len()`` is the vocabulary; turned into an array the row is
    fetched, which only a request that samples asks for."""

    __slots__ = ("token", "_logits", "_index")

    def __init__(self, token: int, logits, index: Optional[int] = None):
        self.token = int(token)
        self._logits = logits        # [vocabulary], or [slots, vocabulary]
        self._index = index          # the slot's row of the latter

    def __len__(self) -> int:
        return self._logits.shape[-1]

    def __array__(self, dtype=None, copy=None):
        row = self._logits if self._index is None \
            else self._logits[self._index]
        return np.asarray(row, dtype=dtype)


class DecodeEngine:
    """Persistent continuous-batching decode loop (module docstring)."""

    def __init__(self, model, *, slots: Optional[int] = None,
                 page: Optional[int] = None,
                 max_len: Optional[int] = None,
                 queue_limit: Optional[int] = None,
                 deadline_ms: Optional[float] = None,
                 admission: Optional[str] = None,
                 eos_token: Optional[int] = None,
                 cache_dtype=None, mesh=None,
                 tenant_qps: Optional[float] = None,
                 tenant_burst: Optional[float] = None,
                 min_step_s: Optional[float] = None,
                 clock=None):
        self.model = model
        if model.params is None:
            model.build()
        self.slots = int(slots if slots is not None
                         else config.get_int("DECODE_SLOTS", 4))
        if self.slots < 1:
            raise ValueError(f"slots must be >= 1, got {self.slots}")
        self.page = int(page if page is not None
                        else config.get_int("DECODE_PAGE", 128))
        model_cap = min((pe.max_len for pe in kv._modules_of_type(
            model, PositionalEmbedding)), default=0)
        cap = int(max_len if max_len is not None
                  else config.get_int("DECODE_MAX_LEN", 0)) or model_cap
        if model_cap and cap > model_cap:
            raise ValueError(f"max_len {cap} > model positional "
                             f"embedding max_len {model_cap}")
        if cap < 1:
            raise ValueError("DecodeEngine needs a positive max_len "
                             "(model has no PositionalEmbedding cap)")
        self.max_len = cap
        self.ladder = page_ladder(self.page, self.max_len)
        self.admission = str(admission if admission is not None else
                             config.get_str("DECODE_ADMISSION",
                                            "continuous"))
        if self.admission not in ("continuous", "batch"):
            raise ValueError(f"admission must be 'continuous' or "
                             f"'batch', got {self.admission!r}")
        self.default_deadline_ms = float(
            deadline_ms if deadline_ms is not None
            else config.get_float("DECODE_DEADLINE_MS", 0.0))
        self.min_step_s = float(
            min_step_s if min_step_s is not None
            else config.get_float("DECODE_MIN_STEP_MS", 0.0) / 1e3)
        self.eos_token = eos_token
        from ..common import get_policy
        self.cache_dtype = cache_dtype or get_policy().compute_dtype
        self.clock = clock or time.monotonic
        self.queue = DecodeQueue(
            int(queue_limit if queue_limit is not None
                else config.get_int("DECODE_QUEUE_LIMIT", 64)),
            clock=self.clock)
        self.quotas = TenantQuotas(tenant_qps or 0.0, burst=tenant_burst,
                                   clock=self.clock)
        self._mesh = mesh
        self._params, self._state = model.params, model.state
        self._replicated = None      # where the token vector lives on a mesh
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            from ..parallel import layout as _layout
            self._params = jax.device_put(
                self._params,
                _layout.assign_shardings(model, self._params, mesh))
            rep = self._replicated = NamedSharding(mesh, PartitionSpec())
            self._state = jax.device_put(
                self._state, jax.tree.map(lambda _: rep, self._state))
        self._module_fp = None       # lazy (fingerprinting traces shapes)
        self._exe: dict = {}         # (kind, *dims) -> compiled
        self._slots: List[Optional[_Seq]] = [None] * self.slots
        self._caches = None
        # each slot's last token as the call before left it on the device,
        # int32[slots]: a prefill sets its slot's row, a step takes the
        # vector as its input and leaves the next one
        self._tokens = jax.device_put(np.zeros(self.slots, np.int32),
                                      self._replicated)
        # the calls whose results the host has not read, oldest first
        self._unread: List[_Call] = []
        self._ticks = 0              # working passes of the loop
        self._cache_len = 0
        self._cache_bytes = 0        # bytes a slot holds at that length
        self._recorder = None
        self._thread: Optional[threading.Thread] = None
        self._lock = threading.Lock()
        # admission in groups (``_prefill_programs``, ``_take_now``): the
        # one guard on a call's memory (the positions it may hold beside
        # the cache are the longest single prompt's the engine must take
        # anyway), and the weights a call streams
        self._group_positions = min(_prompt_bucket(self.max_len - 1),
                                    self.max_len)
        self._weight_bytes = sum(a.nbytes for a in
                                 jax.tree.leaves(self._params))
        self._rows: dict = {}        # (bucket, cache_len) -> rows ladder
        self._hold_until = None      # decode_steps at which a hold ends
        # cumulative counters (stats(); serve.decode telemetry track)
        self.prefill_steps = 0       # device calls of the prefill
        self.prefill_rows = 0        # requests they admitted
        self.slot_steps_held = 0     # slots x steps kept free for a group
        self.prompt_tokens = 0       # real prompt tokens prefilled
        self.prefill_positions = 0   # positions computed for them (pads and
        #                              fill-up rows too)
        self.decode_steps = 0
        # the positions the decode steps' rows stood at, + 1 each (what a
        # row's attention may read), summed over rows and steps; and their
        # mean over the last step's rows
        self.slot_positions = 0
        self._mean_position = 0.0
        # steps called with a call before them unread: the device held its
        # next program when it ended the last one
        self.steps_ahead = 0
        self.tokens_out = 0
        # greedy tokens taken as the device chose them, and rows of
        # log-probabilities fetched for the requests that sample
        self.tokens_device_sampled = 0
        self.logit_rows_fetched = 0
        self.seqs_done = 0
        self.seqs_failed = 0
        self.cache_grows = 0
        # tokens each held expert took (None for a model that counts none)
        # and choices that went to experts held elsewhere, over all calls
        self._expert_tokens: Optional[np.ndarray] = None
        self.expert_tokens_elsewhere = 0
        self._busy_s = 0.0
        # the two kinds of decode state, from the layers' declarations
        # (constants): bytes of fixed size a slot's prefill writes whole,
        # and bytes a slot holds of every position
        one, self._state_bytes = kv.state_bytes_per_row(
            self.model, 1, self.cache_dtype)
        self._position_bytes = one - self._state_bytes
        # request stamps, summed (always on: two clock reads a request)
        self.admitted = 0
        self.queue_wait_s = 0.0      # enqueued -> admitted to a slot
        self.first_tokens = 0
        self.ttft_s = 0.0            # enqueued -> first token sampled

    # -- lifecycle ------------------------------------------------------

    def start(self) -> "DecodeEngine":
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._loop, name="bigdl-decode-engine", daemon=True)
            self._thread.start()
        return self

    def stop(self, drain: bool = True) -> None:
        """Close admissions; ``drain=True`` finishes every queued and
        in-flight sequence first."""
        self.queue.close(drain=drain)
        t = self._thread
        if t is not None:
            t.join(timeout=120.0)
            self._thread = None
        self.queue.fail_pending()

    def __enter__(self) -> "DecodeEngine":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop(drain=not any(exc))

    # -- admission ------------------------------------------------------

    def submit(self, prompt, max_tokens: int, *,
               deadline_ms: Optional[float] = None,
               tenant: Optional[str] = None, priority: int = 0,
               temperature: float = 0.0, top_k: int = 0,
               eos_token=_UNSET, seed: int = 0,
               request_id: Optional[str] = None) -> PendingRequest:
        """Enqueue one sequence; returns a PendingRequest whose
        ``result()`` is the full int32 token row (prompt + generated,
        the ``cached_generate`` contract, truncated at EOS).  Typed
        rejections: ServeError (bad request), QuotaExceeded,
        ServerOverloaded, ServerClosed; RequestTimeout resolves later if
        the time-to-last-token deadline passes in the queue.
        ``request_id`` is the distributed-tracing flow id from the
        ``X-BigDL-Request-Id`` header (minted locally when absent and
        tracing is on)."""
        prompt = np.asarray(prompt, np.int32)
        if prompt.ndim != 1 or prompt.shape[0] == 0:
            raise ServeError("decode: prompt must be a non-empty 1-D "
                             f"token row, got shape {prompt.shape}")
        max_tokens = int(max_tokens)
        if max_tokens < 1:
            raise ServeError(f"decode: max_tokens must be >= 1, got "
                             f"{max_tokens}")
        need = prompt.shape[0] + max_tokens
        if need > self.max_len:
            raise ServeError(
                f"decode: prompt ({prompt.shape[0]}) + max_tokens "
                f"({max_tokens}) exceeds max_len ({self.max_len})")
        self.quotas.admit(tenant)
        eos = self.eos_token if eos_token is _UNSET else eos_token
        dl_ms = self.default_deadline_ms \
            if deadline_ms is None else float(deadline_ms)
        deadline = self.clock() + dl_ms / 1e3 if dl_ms > 0 else None
        gen = {"max_tokens": max_tokens, "temperature": float(temperature),
               "top_k": int(top_k), "seed": int(seed)}
        if eos is not None:
            gen["eos_token"] = int(eos)
        if self._recorder is not None:
            self._recorder.note(prompt, tenant=tenant, priority=priority,
                                deadline_ms=dl_ms if dl_ms > 0 else None,
                                gen=gen)
        payload = dict(gen, prompt=prompt, eos=eos)
        return self.queue.submit(payload, deadline, tenant=tenant,
                                 priority=priority,
                                 request_id=request_id)

    def generate(self, prompt, max_tokens: int,
                 timeout: Optional[float] = 120.0, **kw) -> np.ndarray:
        """Blocking convenience wrapper around :meth:`submit`."""
        return self.submit(prompt, max_tokens, **kw).result(timeout)

    # -- trace recording (server.py contract) ---------------------------

    def record_trace(self, path: Optional[str] = None, *, limit=None):
        from .tracefile import TraceRecorder
        if self._recorder is not None and (path is None or
                                           self._recorder.path == path):
            return self._recorder
        self._recorder = TraceRecorder(clock=self.clock, limit=limit,
                                       path=path)
        return self._recorder

    def stop_trace(self, path: Optional[str] = None):
        rec, self._recorder = self._recorder, None
        if rec is not None and (path or rec.path):
            rec.save(path)
        return rec

    # -- executables (AOT-keyed like _ShardedForward buckets) -----------

    def _key_fields(self, kind: str, jitted, **dims) -> dict:
        """The AOT key of one executable.  The jitted function's name is
        part of it: it is the program's name on the device trace, so a
        warm AOT directory cannot hand back an executable that still runs
        under an older name."""
        fields = dict(aot_mod.base_fingerprint(self._mesh))
        if self._module_fp is None:
            self._module_fp = aot_mod.module_fingerprint(self.model)
        fields["module"] = self._module_fp
        fields["params"] = aot_mod.aval_fingerprint(
            (self._params, self._state))
        fields["kind"] = kind
        fields["program"] = "jit_" + jitted.__name__
        # the key holds the program's name, not its text: both programs
        # return each row's greedy token beside the logits, and take and
        # leave the slots' token vector on the device
        fields["tokens"] = "argmax_carried"
        fields.update(dims)
        return fields

    def _cache_avals(self, cache_len: int):
        return kv.cache_avals(self.model, self.slots, cache_len,
                              self.cache_dtype, self._mesh)

    def _tokens_aval(self):
        return jax.ShapeDtypeStruct((self.slots,), jnp.int32,
                                    sharding=self._replicated)

    def _pin_tokens(self, tokens):
        """On a mesh the token vector leaves a program as the next one
        takes it: whole on every device."""
        if self._replicated is None:
            return tokens
        return jax.lax.with_sharding_constraint(tokens, self._replicated)

    def _step_exe(self, cache_len: int):
        """The decode-step executable for the (slots, cache_len) bucket:
        ALL slots advance one position in one kernel call."""
        memo = ("step", self.slots, cache_len)
        exe = self._exe.get(memo)
        if exe is not None:
            return exe
        model, S, pin = self.model, self.slots, self._pin_tokens

        # named for the device trace: its ``XLA Modules`` line shows this
        # program as ``jit_decode_step``
        @partial(jax.jit, donate_argnums=(2,))
        def decode_step(params, state, caches, tokens, feed):
            # ``tokens``: each row's last token as the call before left it
            # here; ``feed``, from the host: each row's position (-1: an
            # idle row) and, for a row whose token the host chose itself (a
            # request that samples), that token, else -1
            tok = jnp.where(feed[1] >= 0, feed[1], tokens)
            logits, tokens, caches, report = _with_tokens(*kv._slot_step(
                model, params, state, tok, caches, feed[0]))
            return logits, pin(tokens), caches, report

        exe = aot_mod.get_or_compile(
            self._key_fields("decode.step", decode_step, slots=S,
                             cache_len=cache_len,
                             dtype=jnp.dtype(self.cache_dtype).name),
            lambda: decode_step.lower(
                self._params, self._state, self._cache_avals(cache_len),
                self._tokens_aval(),
                jax.ShapeDtypeStruct((2, S), jnp.int32)),
            label="decode.step",
            card_extra={"slots": S, "cache_len": cache_len})
        self._exe[memo] = exe
        return exe

    def _prefill_fn(self, rows: int, prompt_bucket: int, cache_len: int):
        """The prefill's jitted function and the avals it is traced at, for
        a group of up to ``rows`` prompts of one bucket."""
        model, pin = self.model, self._pin_tokens

        # ``jit_decode_prefill`` on the device trace's ``XLA Modules`` line
        @partial(jax.jit, donate_argnums=(2,))
        def decode_prefill(params, state, caches, tokens, toks, slot, t0):
            # the slots' token vector comes back with the group's rows set
            # to their prompts' first tokens: the same pass's step takes
            # them there (a fill-up row's slot is dropped)
            logits, token, caches, report = _with_tokens(*kv._prefill(
                model, params, state, toks, caches, slot, t0))
            return (logits, pin(tokens.at[slot].set(token, mode="drop")),
                    caches, report)

        P = min(prompt_bucket, cache_len)   # cut to the cache where longer
        return decode_prefill, (
            self._params, self._state, self._cache_avals(cache_len),
            self._tokens_aval(), jax.ShapeDtypeStruct((rows, P), jnp.int32),
            jax.ShapeDtypeStruct((rows,), jnp.int32),
            jax.ShapeDtypeStruct((rows,), jnp.int32))

    def _prefill_exe(self, rows: int, prompt_bucket: int, cache_len: int,
                     traced=None):
        """The prefill executable for the (rows, prompt_bucket, slots,
        cache_len) bucket: a group of up to ``rows`` new sequences enters
        as many slots in one pass (models/decode._prefill).  The padded
        bucket, cut to the cache where it is longer, goes through the model
        as [rows, P, E]: every weight is read once a GROUP, each stateful
        layer's leaves land in the group's slots by one scatter each, and
        past the last such layer only each prompt's last real position goes
        on to the head.  Every prompt length in the bucket and every group
        of up to ``rows`` requests shares this compile (``slot`` and ``t0``
        are traced; a row past the group's requests has a slot past the
        cache's rows and writes nothing).  ``traced``: the function as it
        was traced already, where it was."""
        memo = ("prefill", rows, prompt_bucket, self.slots, cache_len)
        exe = self._exe.get(memo)
        if exe is not None:
            return exe
        decode_prefill, avals = self._prefill_fn(rows, prompt_bucket,
                                                 cache_len)
        # ``body``: the key holds the program's name, not its text, and the
        # prefills before this one (a position a call, then one prompt a
        # call) had the same name
        exe = aot_mod.get_or_compile(
            self._key_fields("decode.prefill", decode_prefill,
                             slots=self.slots, cache_len=cache_len,
                             prompt_bucket=prompt_bucket, rows=rows,
                             body="one_pass_group",
                             dtype=jnp.dtype(self.cache_dtype).name),
            lambda: (traced or decode_prefill.trace(*avals)).lower(),
            label="decode.prefill",
            card_extra={"slots": self.slots, "cache_len": cache_len,
                        "prompt_bucket": prompt_bucket, "rows": rows})
        self._exe[memo] = exe
        return exe

    def _call_positions(self, prompt_bucket: int, cache_len: int):
        """The positions (rows x bucket) one prefill call of this bucket may
        carry, and the one-row program as it was traced to count them: as
        many as keep the call's arithmetic (one position's multiply-adds,
        counted from that trace, utils/flops) from outweighing the weight
        bytes it streams (``_FLOPS_PER_WEIGHT_BYTE``), and no more than the
        memory guard allows (``_group_positions``)."""
        fn, avals = self._prefill_fn(1, prompt_bucket, cache_len)
        traced = fn.trace(*avals)
        a_position = max(flops_mod.jaxpr_flops(traced.jaxpr), 1.0) \
            / min(prompt_bucket, cache_len)
        return min(self._group_positions,
                   int(self._weight_bytes * _FLOPS_PER_WEIGHT_BYTE
                       / a_position)), traced

    def _prefill_programs(self, prompt_bucket: int, cache_len: int) -> tuple:
        """The rows a call of this bucket may have: 1, and where the bucket
        groups the widest group's (a smaller group fills that program up).
        Both are compiled by the time this returns: a bucket's group
        program is compiled when the bucket first is, so a burst later
        compiles nothing.  The widest group is a power of two read off what
        the engine can observe, never a setting.  First what needs no trace:
        it is no wider than half the requests a backlogged pass may wait for
        (``_take_cap``: a take spreads over the buckets in use, and a
        fill-up row's positions cost what a request's do), nor than the
        memory guard's positions over the bucket's; where that already
        leaves fewer than ``_MIN_GROUP_ROWS`` the bucket has its one-row
        program and nothing is traced for the rule.  Else one budget of
        positions a call (``_call_positions``) decides."""
        key = (prompt_bucket, cache_len)
        ladder = self._rows.get(key)
        if ladder is None:
            P = min(prompt_bucket, cache_len)
            rows, traced = min(self._take_cap() // 2,
                               self._group_positions // P), None
            if rows >= _MIN_GROUP_ROWS:
                positions, traced = self._call_positions(prompt_bucket,
                                                         cache_len)
                rows = min(rows, positions // P)
            self._prefill_exe(1, prompt_bucket, cache_len, traced)
            ladder = (1,)
            if rows >= _MIN_GROUP_ROWS:
                rows = 1 << (rows.bit_length() - 1)
                self._prefill_exe(rows, prompt_bucket, cache_len)
                ladder = (1, rows)
            self._rows[key] = ladder
        return ladder

    # -- (slots, cache-page) ladder -------------------------------------

    def _bucket_for(self, need: int) -> int:
        for c in self.ladder:
            if c >= need:
                return c
        return self.ladder[-1]

    def _fresh_caches(self, cache_len: int):
        caches = kv.init_kv_cache(self.model, self.slots, cache_len,
                                  self.cache_dtype, mesh=self._mesh)
        return tuple(caches)

    def _ensure_cache(self, need: int, idle: bool) -> None:
        want = self._bucket_for(need)
        if self._caches is None or (idle and want != self._cache_len):
            # idle engine: re-page to exactly what the next admission
            # needs (a 17-token prompt must not pay for max_len), once the
            # last call on the old pages has been read
            self._read()
            self._caches = self._fresh_caches(want)
            self._set_cache_len(want)
            return
        if want > self._cache_len:
            # grow to the next page: each leaf padded with zeros along its
            # own length axis — masked positions carry exact-zero softmax
            # weight, so the in-flight slots decode on unchanged
            self._caches = kv.grow_cache(self.model, self._caches, want,
                                         self._mesh)
            self._set_cache_len(want)
            self.cache_grows += 1

    def _set_cache_len(self, cache_len: int) -> None:
        """The cache's length and what follows from it: reckoned where it
        changes (a walk over the model's modules), read every tick."""
        self._cache_len = cache_len
        self._cache_bytes = kv.state_bytes_per_row(
            self.model, cache_len, self.cache_dtype)[0]

    def cache_bytes_per_slot(self) -> int:
        """Bytes of decode state one slot holds at the present cache
        length, leaves of both kinds, from the layers' declarations
        (nothing is read off the device; 0 before the first admission)."""
        return self._cache_bytes

    def state_bytes_per_slot(self) -> int:
        """The part of ``cache_bytes_per_slot`` that is of fixed size
        whatever the length (recurrent state; 0 for a model of keys and
        values alone)."""
        return self._state_bytes

    # -- the persistent step loop ---------------------------------------

    def _loop(self) -> None:
        telemetry.thread_name("decode engine")
        while True:
            try:
                if not self._tick():
                    return
            except Exception as e:  # noqa: BLE001 — engine must survive
                # backstop: a fault not attributable to one slot fails
                # every in-flight sequence typed rather than wedging the
                # loop (the queue keeps serving future ticks); what was
                # called for them and not read is dropped
                waiting = [seq for c in self._unread for seq, _pos in c.rows]
                del self._unread[:]
                for seq in waiting + [x for x in self._slots if x is not None]:
                    self._fail(seq, e)

    def _fail(self, seq: _Seq, err: Exception) -> None:
        """The sequence fails typed and its slot, if it still holds one, is
        free; rows of it that wait unread are counted nowhere."""
        if seq.over:
            return
        if seq.req.rid is not None:
            # the fault lands on the request's flow (failover segment)
            telemetry.flow_step(seq.req.rid, hop="decode.fault",
                                slot=seq.slot, error=type(err).__name__)
        seq.req._resolve(error=err, now=self.clock())
        self._leave(seq)
        self.seqs_failed += 1

    def _finish(self, seq: _Seq) -> None:
        out = seq.buf[: seq.t0 + seq.emitted].copy()
        if seq.routed is not None:
            seq.req.routing = seq.routed[:, : len(out) - 1]
        seq.req._resolve(result=out, version="decode", now=self.clock())
        reg = metrics_export._REGISTRY
        if reg is not None and seq.req.latency_s is not None:
            reg.observe("bigdl_decode_ttlt_seconds", seq.req.latency_s,
                        help="time to last token (submit to full row), "
                             "seconds")
        self._leave(seq)
        self.seqs_done += 1

    def _leave(self, seq: _Seq) -> None:
        """The sequence is answered.  Its slot is free, unless its last
        call freed it already (and a next sequence may hold it by now)."""
        seq.over = True
        if self._slots[seq.slot] is seq:
            self._slots[seq.slot] = None

    def _stamp_admitted(self, req: PendingRequest) -> None:
        req.admitted = self.clock()
        wait = max(req.admitted - req.enqueued, 0.0)
        self.admitted += 1
        self.queue_wait_s += wait
        reg = metrics_export._REGISTRY
        if reg is not None:
            reg.observe("bigdl_decode_queue_wait_seconds", wait,
                        help="submit to admission into a slot, seconds")

    def _stamp_first_token(self, req: PendingRequest) -> None:
        req.first_token = self.clock()
        ttft = max(req.first_token - req.enqueued, 0.0)
        self.first_tokens += 1
        self.ttft_s += ttft
        reg = metrics_export._REGISTRY
        if reg is not None:
            reg.observe("bigdl_decode_ttft_seconds", ttft,
                        help="time to first token (submit to the first "
                             "sampled token), seconds")
        if req.rid is not None:
            # the one flow step of a request's tokens: the later ones are
            # the ticks' spans, one token a tick for every active slot
            telemetry.flow_step(req.rid, hop="decode.first_token")

    def _count_experts(self, counts) -> None:
        """Fold one call's expert token counts (held experts, then the
        choices that went elsewhere) into the running ones."""
        if counts is None:
            return
        counts = np.asarray(counts).astype(np.int64)
        if self._expert_tokens is None:
            self._expert_tokens = np.zeros(len(counts) - 1, np.int64)
        self._expert_tokens += counts[:-1]
        self.expert_tokens_elsewhere += int(counts[-1])
        reg = metrics_export._REGISTRY
        if reg is not None:
            help_ = "routed expert choices of live tokens, by where the " \
                    "expert is held"
            reg.counter_inc("bigdl_decode_expert_tokens_total",
                            float(counts[:-1].sum()), help=help_,
                            held="here")
            reg.counter_inc("bigdl_decode_expert_tokens_total",
                            float(counts[-1]), help=help_, held="elsewhere")
            reg.gauge_set("bigdl_decode_expert_imbalance",
                          self._expert_imbalance(),
                          help="busiest held expert's tokens over the "
                               "held experts' mean, since start")

    def _expert_imbalance(self) -> float:
        t = self._expert_tokens
        return float(t.max() / max(t.mean(), 1e-9)) if t is not None else 0.0

    def _expert_stats(self) -> dict:
        """The expert counters since start ({} for a model that counts
        none): for ``stats()`` and the ``serve.decode`` track alike."""
        if self._expert_tokens is None:
            return {}
        return {"expert_tokens": int(self._expert_tokens.sum()),
                "expert_tokens_elsewhere": self.expert_tokens_elsewhere,
                "expert_tokens_max": int(self._expert_tokens.max())}

    def _fetch(self, n: int):
        """The one ``jax.device_get`` of what the oldest ``n`` unread calls
        left on the device: their tokens and what the expert layers report
        (the logits stay there).  The host blocked on the device, on the
        newest of those calls, and on the transfer, as ``decode.fetch``.
        Returns the calls beside what came down, for :meth:`_take`; None
        where nothing waits."""
        if n <= 0:
            return None
        calls = self._unread[:n]
        out = [(c.tokens, c.report or (None, None)) for c in calls]
        nbytes = sum(a.nbytes for a in jax.tree.leaves(out)) \
            if telemetry.get_active() is not None else 0
        with telemetry.span("decode.fetch", cat="serve",
                            program=calls[-1].program, tick=calls[-1].tick,
                            calls=n, bytes=nbytes):
            return calls, jax.device_get(out)

    def _take(self, fetched) -> None:
        """Give every row of the fetched calls its token, as
        ``decode.sample``: first tokens are stamped and finished requests
        answered here, a call after the one that computed them."""
        if fetched is None:
            return
        calls, got = fetched
        with telemetry.span("decode.sample", cat="serve",
                            active=sum(len(c.rows) for c in calls)):
            for c, (tokens, (counts, chosen)) in zip(calls, got):
                self._count_experts(counts)
                for i, (seq, pos) in enumerate(c.rows):
                    if seq.over:
                        # it ended at an EOS the call before, or failed:
                        # the row was computed for nobody
                        continue
                    if pos is None:          # row i of a prefill's group
                        self._prompt_routing(seq, chosen, i)
                        row = _LogitRow(tokens[seq.slot], c.logits, i)
                    else:
                        if chosen is not None:
                            seq.routed[:, pos] = chosen[:, seq.slot]
                        row = _LogitRow(tokens[seq.slot], c.logits, seq.slot)
                    self._advance(seq, self._sample(seq, row))
        # only now: a fault above finds every sequence in the backstop
        del self._unread[:len(calls)]

    def _read(self) -> None:
        """Nothing stays unread: before an idle re-page, and before a step
        that needs a token the host alone can choose."""
        self._take(self._fetch(len(self._unread)))

    def _prompt_routing(self, seq: _Seq, chosen, row: int) -> None:
        """Row ``row`` of a prefill's report of the experts its positions
        chose (``[rows, positions, k]`` a layer), into the sequence's
        ``[layers, positions, k]``: a layer saw the whole bucket (its pads
        go) or the prompt's last position alone."""
        if chosen is None:
            return
        t0 = seq.t0
        seq.routed = np.full(
            (len(chosen), t0 + seq.max_tokens, chosen[0].shape[-1]),
            -1, np.int32)
        for layer, a in enumerate(chosen):
            a = a[row]
            if len(a) == 1:
                seq.routed[layer, t0 - 1] = a[0]
            else:
                seq.routed[layer, :t0] = a[:t0]

    def _sample(self, seq: _Seq, logits_row: _LogitRow) -> int:
        """The one place every served token passes through.  A greedy
        request takes the token the device chose; one that samples has its
        row fetched and goes through ``sample_next``."""
        if seq.temperature <= 0:
            self.tokens_device_sampled += 1
            return logits_row.token
        self.logit_rows_fetched += 1
        tok, seq.rng = sample_next(np.asarray(logits_row)[None],
                                   seq.temperature, seq.top_k, seq.rng)
        return int(tok[0])

    def _advance(self, seq: _Seq, tok: int) -> None:
        """Record one token the host has read; the sequence is answered as
        soon as that token is its EOS or its budget's last."""
        seq.buf[seq.t0 + seq.emitted] = tok
        seq.emitted += 1
        self.tokens_out += 1
        if seq.emitted == 1:
            self._stamp_first_token(seq.req)
        if (seq.eos is not None and tok == seq.eos) or \
                seq.emitted >= seq.max_tokens:
            self._finish(seq)

    def _called(self, call: _Call) -> None:
        """A call is on the device: its tokens are the next call's, its rows
        wait to be read, and a row that was its sequence's last frees the
        slot now, for the next pass's admission."""
        self._tokens = call.tokens
        self._unread.append(call)
        for seq, _pos in call.rows:
            seq.called += 1
            if seq.called >= seq.max_tokens:
                self._slots[seq.slot] = None

    def _enter(self, req: PendingRequest, s: int) -> Optional[_Seq]:
        """The request holds slot ``s`` from here on; None where the slot's
        chaos point faulted it (it fails alone, typed)."""
        p = req.payload
        rng = jax.random.PRNGKey(p.get("seed", 0)) \
            if p.get("temperature", 0.0) > 0 else None
        seq = _Seq(req, s, p["prompt"], p["max_tokens"], p.get("eos"),
                   p.get("temperature", 0.0), p.get("top_k", 0), rng)
        self._slots[s] = seq
        self._stamp_admitted(req)
        if req.rid is not None:
            telemetry.flow_step(req.rid, hop="decode.admit", slot=s,
                                prompt_len=seq.t0)
        try:
            chaos.fire(f"serve.decode@{s}", thread_exc=SlotFault)
        except Exception as e:  # noqa: BLE001 — typed per-sequence fail
            self._fail(seq, e)
            return None
        return seq

    def _admit(self, reqs, free) -> None:
        """A pass's requests enter the free slots, in the order they were
        taken, and their prefills are called: the requests of one prompt
        bucket share calls, as many a call as the bucket's widest program
        has rows (``_prefill_programs``); first tokens are read a call
        later."""
        buckets: dict = {}
        for req in reqs:
            seq = self._enter(req, free.pop(0))
            if seq is not None:
                buckets.setdefault(_prompt_bucket(seq.t0), []).append(seq)
        for pb, seqs in buckets.items():
            ladder = self._prefill_programs(pb, self._cache_len)
            for i in range(0, len(seqs), ladder[-1]):
                group = seqs[i:i + ladder[-1]]
                self._prefill(pb, group,
                              min(r for r in ladder if r >= len(group)))

    def _prefill(self, pb: int, group, rows: int) -> None:
        """One prefill call for ``group``, sequences of prompt bucket
        ``pb``, by the program of ``rows`` rows (no fewer than the group):
        the rows past the group fill the program up, with a slot past the
        cache's, and write nothing.  A call that fails fails its group."""
        P = min(pb, self._cache_len)   # the bucket, cut to the cache
        real = sum(seq.t0 for seq in group)
        with telemetry.span("decode.admit", cat="serve", prompt_len=real,
                            bucket=pb, slot=group[0].slot, rows=len(group),
                            state_bytes=self._state_bytes):
            # the host's part, up to the executable's return
            with telemetry.span("decode.call", cat="serve",
                                program="decode_prefill"):
                toks = np.zeros((rows, P), np.int32)
                slot = np.full(rows, self.slots, np.int32)
                t0 = np.zeros(rows, np.int32)
                for i, seq in enumerate(group):
                    toks[i, :seq.t0] = seq.buf[:seq.t0]
                    slot[i], t0[i] = seq.slot, seq.t0
                exe = self._prefill_exe(rows, pb, self._cache_len)
                try:
                    logits, tokens, self._caches, report = exe(
                        self._params, self._state, self._caches,
                        self._tokens, jnp.asarray(toks), jnp.asarray(slot),
                        jnp.asarray(t0))
                except Exception as e:  # noqa: BLE001
                    for seq in group:
                        self._fail(seq, SlotFault(
                            f"decode: prefill failed in slot {seq.slot}: "
                            f"{e!r}"))
                    return
            self._called(_Call(self._ticks, "decode_prefill",
                               [(seq, None) for seq in group], logits,
                               tokens, report))
            self.prefill_steps += 1
            self.prefill_rows += len(group)
            self.prompt_tokens += real
            self.prefill_positions += toks.size

    def _step(self, rows) -> bool:
        """Call the decode step for ``rows``, the sequence of every slot
        taken: one position forward each, in ONE kernel call.  True
        when a call before it was unread: the device holds this one by the
        time it ends that one."""
        # the host's part, up to the executable's return
        with telemetry.span("decode.call", cat="serve",
                            program="decode_step"):
            # positions (-1: an idle row) over the tokens the host chose
            # itself (-1: the device's own, which never came down)
            feed = np.full((2, self.slots), -1, np.int32)
            at = []
            for seq in rows:
                pos = seq.t0 + seq.called - 1
                feed[0, seq.slot] = pos
                if seq.temperature > 0:
                    feed[1, seq.slot] = seq.buf[pos]
                at.append((seq, pos))
            exe = self._step_exe(self._cache_len)
            logits, tokens, self._caches, report = exe(
                self._params, self._state, self._caches, self._tokens,
                jnp.asarray(feed))
        ahead = bool(self._unread)
        self._called(_Call(self._ticks, "decode_step", at, logits, tokens,
                           report))
        self.decode_steps += 1
        reach = sum(pos + 1 for _seq, pos in at)
        self.slot_positions += reach
        self._mean_position = reach / len(at)
        self.steps_ahead += ahead
        return ahead

    def _tick(self) -> bool:
        """One loop iteration: admit into free slots, decode all active
        slots in one kernel call, read what the pass before called.
        Returns False when closed + drained."""
        q = self.queue
        free = [s for s in range(self.slots) if self._slots[s] is None]
        n_active = self.slots - len(free)
        incoming: List[PendingRequest] = []
        if free and (self.admission == "continuous" or n_active == 0):
            incoming = q.take(self._take_now(len(free), q.depth()))
        if n_active == 0 and not incoming and not self._unread:
            if q.closed and q.depth() == 0:
                return False
            q.wait_for_work(DecodeQueue._SLICE)
            return True
        with telemetry.span("decode.tick", cat="serve", active=n_active,
                            admitted=len(incoming)):
            self._work(q, free, n_active, incoming)
        return True

    def _take_cap(self) -> int:
        """The most requests a backlogged pass may wait for: slots freed
        one after another wait (n - 1) / 2 steps each for the n-th, and
        that may keep no more than ``_HELD_SHARE`` of the slots free on
        average."""
        return 1 + int(2 * _HELD_SHARE * self.slots)

    def _group_target(self) -> int:
        """How many requests a backlogged pass waits to take at once: as
        many as ``_take_cap`` allows where some prompt bucket has a group
        program (at this cache length), since a take spreads over the
        buckets in use and a fuller take makes fuller groups; 1 where none
        groups: nothing is held."""
        groups = any(cache_len == self._cache_len and len(ladder) > 1
                     for (_pb, cache_len), ladder in self._rows.items())
        return self._take_cap() if groups else 1

    def _take_now(self, free: int, waiting: int) -> int:
        """How many requests this pass takes, with ``free`` slots free and
        ``waiting`` requests queued.  No backlog (no more wait than slots
        are free): all of them, at once, so a lone request on an idle engine
        is called in the pass that takes it.  Under a backlog the free slots
        are held for a fuller take (``_group_target``), but no longer than
        the taken slots' own remaining counts (``max_tokens - called``) said
        the take would need to fill when the hold began; 0: held."""
        want = min(self._group_target(), waiting)
        if free >= want:              # all that wait, or a full take
            self._hold_until = None
            return free
        if self._hold_until is None:
            left = sorted(seq.max_tokens - seq.called
                          for seq in self._slots if seq is not None)
            self._hold_until = self.decode_steps + left[want - free - 1]
        if self.decode_steps >= self._hold_until:
            self._hold_until = None
            return free
        self.slot_steps_held += free
        return 0

    def _work(self, q, free, n_active, incoming) -> None:
        """What one pass does once there is something to do (the
        ``decode.tick`` span).  It calls, then reads: the admissions'
        prefills, one decode step for every slot taken, and only then the
        results of the calls the pass before made, so the device holds its
        next program whenever it ends one.  What cannot run ahead reads
        first: a slot whose request samples has its next token on the host
        alone."""
        t_start = self.clock()
        tokens_before = self.tokens_out
        self._ticks += 1
        if incoming:
            need = max(len(r.payload["prompt"]) + r.payload["max_tokens"]
                       for r in incoming)
            self._ensure_cache(need, idle=(n_active == 0))
        # the calls of the passes before: read once this pass has called
        old = len(self._unread)
        self._admit(incoming, free)
        # every slot still taken (a freshly prefilled one too: its first
        # token lies in the token vector) goes one position forward
        rows = [seq for seq in self._slots if seq is not None]
        for seq in list(rows):
            try:
                chaos.fire(f"serve.decode@{seq.slot}", thread_exc=SlotFault)
            except Exception as e:  # noqa: BLE001
                self._fail(seq, e)
                rows.remove(seq)
        if any(seq.temperature > 0 for seq in rows):
            # a sampled token exists on the host alone, so this pass
            # reads, then calls (a read can end a row: its EOS, seen now)
            self._read()
            old = 0
            rows = [seq for seq in rows if not seq.over]
        ahead = None
        if rows:
            # the step's call, and the host blocked on the calls before it
            with telemetry.span("decode.step", cat="serve",
                                active=len(rows)):
                ahead = self._step(rows)
                fetched = self._fetch(old)
        else:
            # nothing to call: what waits is all there is to do
            fetched = self._fetch(old)
        self._take(fetched)
        dt = self.clock() - t_start
        if self.min_step_s > 0 and dt < self.min_step_s:
            time.sleep(self.min_step_s - dt)
            dt = self.min_step_s
        self._busy_s += dt
        q.note_service(max(self.tokens_out - tokens_before, 1), dt)
        if telemetry.get_active() is None \
                and metrics_export._REGISTRY is None:
            return      # nothing reads the track: compute none of it
        n_active = sum(1 for s in self._slots if s is not None)
        steps = self.prefill_steps + self.decode_steps
        # 1: this pass's step was called with a call before it unread; 0:
        # it read first (a slot samples) or the device had nothing
        ran = {} if ahead is None else {"ran_ahead": float(ahead)}
        telemetry.counter(
            "serve.decode", **self._expert_stats(), **ran,
            tokens_per_s=self.tokens_out / max(self._busy_s, 1e-9),
            tokens_device_sampled=self.tokens_device_sampled,
            logit_rows_fetched=self.logit_rows_fetched,
            fill=n_active / self.slots,
            prefill_frac=self.prefill_steps / max(steps, 1),
            prefill_group=self.prefill_rows / max(self.prefill_steps, 1),
            held_share=self.slot_steps_held
            / max(self.decode_steps * self.slots, 1),
            prefill_pad_frac=1.0 - self.prompt_tokens
            / max(self.prefill_positions, 1),
            decode_frac=self.decode_steps / max(steps, 1),
            cache_bytes_per_slot=self._cache_bytes,
            state_bytes_per_slot=self._state_bytes,
            state_bytes_fixed=self.slots * self._state_bytes,
            state_bytes_per_position=self._position_bytes,
            mean_position=self._mean_position,
            cache_len=self._cache_len)
        reg = metrics_export._REGISTRY
        if reg is not None:
            reg.gauge_set("bigdl_decode_state_bytes_per_slot",
                          float(self._state_bytes),
                          help="decode state of fixed size a slot holds "
                               "whatever the length (recurrent state), bytes")

    # -- introspection --------------------------------------------------

    def tokens_per_s(self) -> float:
        return self.tokens_out / max(self._busy_s, 1e-9)

    def stats(self) -> dict:
        s = aot_mod.stats()
        out = {
            "slots": self.slots,
            "active": sum(1 for x in self._slots if x is not None),
            "admission": self.admission,
            "cache_len": self._cache_len,
            "cache_bytes_per_slot": self._cache_bytes,
            "state_bytes_per_slot": self._state_bytes,
            "state_bytes_fixed": self.slots * self._state_bytes,
            "state_bytes_per_position": self._position_bytes,
            "cache_grows": self.cache_grows,
            "prefill_steps": self.prefill_steps,
            "prefill_rows": self.prefill_rows,
            "slot_steps_held": self.slot_steps_held,
            "prompt_tokens": self.prompt_tokens,
            "prefill_positions": self.prefill_positions,
            "decode_steps": self.decode_steps,
            "slot_positions": self.slot_positions,
            "steps_ahead": self.steps_ahead,
            "tokens_out": self.tokens_out,
            "tokens_device_sampled": self.tokens_device_sampled,
            "logit_rows_fetched": self.logit_rows_fetched,
            "tokens_per_s": round(self.tokens_per_s(), 3),
            "seqs_done": self.seqs_done,
            "seqs_failed": self.seqs_failed,
            "admitted": self.admitted,
            "queue_wait_s": self.queue_wait_s,
            "first_tokens": self.first_tokens,
            "ttft_s": self.ttft_s,
            "queue": self.queue.stats(),
            "quota": self.quotas.stats(),
            "aot": {k: int(s[k]) for k in ("hits", "misses", "stores",
                                           "lowers", "compiles",
                                           "corrupt")},
        }
        out.update(self._expert_stats())
        cards = hlostats.ledger()
        if cards:
            out["compile_cards"] = cards
        if self._recorder is not None:
            out["trace_recording"] = self._recorder.stats()
        return out
