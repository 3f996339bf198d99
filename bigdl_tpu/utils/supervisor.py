"""Training-run supervision: stall watchdog + multi-host liveness.

Reference gap this closes: BigDL inherited liveness from Spark — a dead
executor fails the synchronous job and the driver retries
(DistriOptimizer.scala:750-816) — but a compiled async backend has no
such umpire: a hung collective, a device call that never returns, or a dead peer
process hangs training *silently and forever*, the one failure mode the
checkpoint-lineage machinery (docs/robustness.md) cannot reach because
no exception is ever raised.  TF's supervisor/monitored-session design
(arxiv 1605.08695) shows the shape reproduced here: phase-tagged
heartbeats, per-phase deadlines, and a diagnostic dump on stall.

Core pieces
-----------
- :class:`Supervisor`: a daemon monitor thread watching phase-tagged
  heartbeats (``beat("data"|"step"|"checkpoint"|"validation")``) from the
  supervised loop.  Per-phase deadlines come from the constructor or the
  ``BIGDL_TPU_SUPERVISE_<PHASE>`` / ``_SUPERVISE_DEADLINE`` env knobs;
  the clock is injectable (like ``BIGDL_TPU_IO_*``'s timebase) so tests
  run wall-clock-free.
- On a missed deadline the supervisor writes a JSON **crash report**
  (all-thread stack dumps via ``sys._current_frames`` — plus a
  best-effort ``faulthandler`` dump for local dirs — the heartbeat
  timeline, ``chaos.counts()``, platform info, stale peers) next to the
  checkpoint dir via ``file_io`` (works on local, ``memory://``, any
  fsspec scheme), then acts per policy:

  * ``raise`` (default): async-raises a typed :class:`StallError` into
    the supervised thread (the most recent beater), which lands in the
    optimizer's existing retry machinery — recovery resumes from the
    checkpoint lineage.  The raise takes effect at the next Python
    bytecode; a backend wedged inside one C call never reaches one,
    which is what ``exit`` is for.
  * ``exit``: ``os._exit(86)`` after the report — for wedged backends
    where Python can't unwind (utils/timing.py documents exactly such a
    backend: ``block_until_ready`` returns while the RPC never does).
  * ``on_stall`` callback: the embedder owns the response (a tool that
    emits partial results and exits is this supervisor with a callback:
    one liveness mechanism, not two).

- Auxiliary **channels** (:meth:`Supervisor.channel`): background workers
  of the supervised loop — the input-pipeline prefetch thread
  (dataset/prefetch.py) — heartbeat their own slot, watched against the
  same per-phase deadlines.  A stalled worker trips its phase deadline
  even while the main thread is busy inside a step (and a busy worker
  can never mask a stalled main loop); the StallError is async-raised
  into the WORKER, which forwards it to the consumer's ``next()``.

- Multi-host liveness: each process publishes a heartbeat file
  (``<peer_dir>/heartbeat.<rank>``, JSON with the last beat's wall time
  AND the monitor's publication wall time) through ``file_io``; every
  supervisor flags peers whose BEATS go stale
  (``BIGDL_TPU_SUPERVISE_PEER_STALE`` seconds), so an eternal allgather
  hang dies with "host 3 last seen 94s ago" in the crash report instead
  of hanging forever.  Publication happens from the MONITOR thread but
  stamps the supervised thread's last-beat time — a stalled rank goes
  stale on its peers even while its monitor lives.  Publication is
  best-effort and RETRIED: a transient store flake is counted
  (``heartbeat_errors``) and re-attempted on the next poll, never
  allowed to kill the monitor or silently stop beats.

- Elastic host-loss promotion (parallel/elastic): with
  ``BIGDL_TPU_ELASTIC_PEER_LOST`` armed, a peer whose *publication*
  (not just beats — a compiling or wedged rank still publishes) goes
  silent past that threshold is promoted to a typed ``PeerLostError``
  async-raised into the supervised thread, and an epoch-stamped
  ``elastic/recover.<rank>`` intent file is published so the other
  survivors converge on their next poll.  The optimizer's retry loop
  turns that into negotiate -> re-form -> resume (docs/robustness.md).

Knobs (utils/config tier):

| env var | meaning | default |
|---|---|---|
| ``BIGDL_TPU_SUPERVISE_DATA/_STEP/_CHECKPOINT/_VALIDATION`` | per-phase deadline seconds (0 = unwatched) | 0 |
| ``BIGDL_TPU_SUPERVISE_DEADLINE`` | default deadline for phases without their own | 0 |
| ``BIGDL_TPU_SUPERVISE_POLICY`` | ``raise`` or ``exit`` | raise |
| ``BIGDL_TPU_SUPERVISE_PEER_STALE`` | peer heartbeat (beat-age) staleness threshold, seconds | 60 |
| ``BIGDL_TPU_ELASTIC_PEER_LOST`` | publication-silence seconds promoting a peer to LOST (0 = off) | 0 |
| ``BIGDL_TPU_ELASTIC_REFORM_GRACE`` | post-reform seconds during which silence is NOT promoted to loss (members recompile their jitted step after every shrink/grow) | 2 |
"""

from __future__ import annotations

import collections
import json
import logging
import os
import sys
import threading
import time
import traceback
from typing import Callable, Dict, Optional

from . import chaos, config

logger = logging.getLogger("bigdl_tpu")

__all__ = ["StallError", "Supervisor", "PHASES", "notify", "set_active",
           "get_active", "env_deadlines"]

#: the optimizer loop's heartbeat phases.  "compile" tags the FIRST step
#: of each attempt (it holds the XLA compile — ~25s for LeNet on a TPU
#: backend — and must not false-trip a tight steady-state "step"
#: deadline); it is unwatched unless given its own deadline.  "serve" is
#: the online inference subsystem's replica-worker phase
#: (serve/server.py — each replica heartbeats its own channel).
PHASES = ("data", "step", "compile", "checkpoint", "validation", "serve")

# PyThreadState_SetAsyncExc raises the exception CLASS with no args in the
# target thread; the class pulls its message from here so the StallError
# the optimizer catches still names the phase/deadline/stale peers.
_LAST_STALL = {"message": None}


class StallError(RuntimeError):
    """A supervision deadline was missed: the run is hung, not crashed.

    Raised (asynchronously) into the supervised thread so the optimizer's
    retry loop treats the hang like any transient failure — recover from
    the checkpoint lineage and continue."""

    def __init__(self, *args):
        if not args and _LAST_STALL["message"]:
            args = (_LAST_STALL["message"],)
        super().__init__(*args or
                         ("training run stalled (supervision deadline "
                          "missed)",))


def _async_raise(thread_id: int, exc_class) -> bool:
    """Schedule `exc_class` to be raised in `thread_id` at its next
    bytecode boundary (CPython PyThreadState_SetAsyncExc)."""
    import ctypes
    res = ctypes.pythonapi.PyThreadState_SetAsyncExc(
        ctypes.c_ulong(thread_id), ctypes.py_object(exc_class))
    if res > 1:  # delivered to >1 thread state: undo, report failure
        ctypes.pythonapi.PyThreadState_SetAsyncExc(
            ctypes.c_ulong(thread_id), None)
        return False
    return res == 1


def env_deadlines():
    """(per-phase deadlines dict, default deadline or None) from the
    ``BIGDL_TPU_SUPERVISE_*`` env knobs."""
    deadlines = {}
    for phase in PHASES:
        v = config.get_float("SUPERVISE_" + phase.upper(), 0.0)
        if v > 0:
            deadlines[phase] = v
    default = config.get_float("SUPERVISE_DEADLINE", 0.0)
    return deadlines, (default if default > 0 else None)


# process-default supervisor: low-level helpers (utils/timing's measure
# loops) refresh it via notify() without threading a handle through every
# call chain — benches get stall coverage for free
_ACTIVE: Optional["Supervisor"] = None


def set_active(sup: Optional["Supervisor"]) -> None:
    global _ACTIVE
    _ACTIVE = sup


def get_active() -> Optional["Supervisor"]:
    return _ACTIVE


def notify(phase: Optional[str] = None) -> None:
    """Heartbeat the process-default supervisor (no-op when none is
    active).  phase=None refreshes the current phase's timer without
    changing it — the generic progress-callback semantic."""
    sup = _ACTIVE
    if sup is not None:
        sup.beat(phase)


class _Channel:
    """Heartbeat handle for one auxiliary supervised thread (see
    Supervisor.channel).  beat(None) refreshes the timer without changing
    the phase; close() retires the slot (idempotent)."""

    __slots__ = ("_sup", "name")

    def __init__(self, sup: "Supervisor", name: str):
        self._sup = sup
        self.name = name

    def beat(self, phase: Optional[str] = None) -> None:
        self._sup._beat_channel(self.name, phase)

    def close(self) -> None:
        self._sup._close_channel(self.name)


def _platform_info() -> dict:
    """Best-effort environment snapshot for the crash report.  Must never
    touch the backend (jax.devices() can hang — it may be WHY we are
    here); only already-materialized facts."""
    import platform as _platform
    info = {"python": sys.version.split()[0],
            "platform": _platform.platform(),
            "pid": os.getpid(),
            "jax_platforms_env": os.environ.get("JAX_PLATFORMS")}
    jx = sys.modules.get("jax")
    if jx is not None:
        info["jax"] = getattr(jx, "__version__", "?")
    return info


class Supervisor:
    """Phase-tagged heartbeat watchdog with per-phase deadlines.

    Usage (the Optimizer wires this automatically when supervision is
    configured)::

        sup = Supervisor({"step": 120, "data": 60}, report_dir=ckpt_dir)
        sup.start()
        ...
        sup.beat("data"); batch = next(it)
        sup.beat("step"); loss = step(batch)
        ...
        sup.stop()

    Deadline lookup: exact phase name, else the prefix before ``:``
    (stages like ``compile:resnet50``), else `default_deadline`;
    None/0 means the phase is unwatched."""

    def __init__(self, deadlines: Optional[Dict[str, float]] = None,
                 default_deadline: Optional[float] = None, *,
                 report_dir: Optional[str] = None,
                 policy: Optional[str] = None,
                 on_stall: Optional[Callable[[dict], bool]] = None,
                 poll_interval: Optional[float] = None,
                 clock=None, sleep=None, wall_clock=None,
                 peer_dir: Optional[str] = None,
                 rank: int = 0, world: int = 1,
                 peer_stale: Optional[float] = None,
                 publish_interval: Optional[float] = None,
                 peer_lost: Optional[float] = None,
                 lineage_dir: Optional[str] = None,
                 on_peer_stale: Optional[Callable[[int, float],
                                                  None]] = None,
                 on_peer_returned: Optional[Callable[[int, int],
                                                     None]] = None,
                 generation: int = 0,
                 name: str = "bigdl-supervisor",
                 timeline_len: int = 64):
        self.deadlines = dict(deadlines or {})
        self.default_deadline = default_deadline
        self.report_dir = report_dir
        self.policy = policy or config.get_str("SUPERVISE_POLICY", "raise")
        if self.policy not in ("raise", "exit"):
            # a typo'd policy silently reverting to 'raise' would leave a
            # wedged backend hanging — exactly what 'exit' exists for
            raise ValueError(f"supervisor: unknown policy {self.policy!r} "
                             "(expected 'raise' or 'exit')")
        self.on_stall = on_stall
        self.clock = clock or time.monotonic
        self.wall_clock = wall_clock or time.time
        self.poll_interval = poll_interval
        self.peer_dir = peer_dir
        self.rank, self.world = int(rank), int(world)
        self.peer_stale = (peer_stale if peer_stale is not None
                           else config.get_float("SUPERVISE_PEER_STALE",
                                                 60.0))
        self.publish_interval = publish_interval
        # elastic host-loss promotion (parallel/elastic): peer_lost is the
        # PUBLICATION-silence threshold (0 = off); elastic_dir holds the
        # recover.<rank>/lineage.<rank> protocol files (usually
        # <ckpt>/elastic); on_peer_stale fires once per peer per stale
        # episode (programmatic access beside the log line)
        self.peer_lost = (peer_lost if peer_lost is not None
                          else config.get_float("ELASTIC_PEER_LOST", 0.0))
        # detection grace after every re-form: all members tear down and
        # recompile their jitted step right after a shrink/grow, and a
        # compile can starve the monitor thread past a tight peer_lost
        # threshold — silence inside this window is rebuild, not death
        self.reform_grace = config.get_float("ELASTIC_REFORM_GRACE", 2.0)
        self._promotion_grace_until = 0.0
        #: the CHECKPOINT/lineage dir whose `elastic/` subdir carries the
        #: recovery protocol files (parallel/elastic.elastic_dir)
        self.lineage_dir = lineage_dir
        self.on_peer_stale = on_peer_stale
        # on_peer_returned fires ONCE per returned-peer episode (mirror of
        # on_peer_stale): a rank recovered away from has published a
        # heartbeat with a HIGHER generation than the frozen one it left
        # behind — it wants back in (parallel/elastic grow).  `generation`
        # is stamped into this rank's own heartbeat blob; a joiner bumps
        # it past its previous life's so survivors can tell "came back"
        # from "stale file".
        self.on_peer_returned = on_peer_returned
        self.generation = int(generation)
        self.elastic_epoch = 0      # completed elastic recovery rounds
        self.heartbeat_errors = 0   # failed (retried) heartbeat publishes
        self._publish_suspended = False
        # ranks already recovered away from -> the heartbeat generation
        # last seen from them (membership test unchanged; the value is
        # what a RETURN must exceed)
        self._lost_peers: Dict[int, int] = {}
        self._returned_peers: Dict[int, int] = {}
        self._peer_gens: Dict[int, int] = {}
        self._peer_lost_pending = False
        self._lost_candidates: Dict[int, float] = {}
        self.name = name
        self._lock = threading.Lock()
        self._timeline = collections.deque(maxlen=timeline_len)
        self._count = 0
        self._last = ("init", self.clock())
        self._thread_id = threading.get_ident()
        # auxiliary supervised threads (e.g. the input-pipeline prefetch
        # worker): name -> [phase, last_beat, thread_id, beat_count].
        # Kept OUT of the main slot/timeline so a worker's liveness can
        # never mask a stalled main loop (and vice versa) — every channel
        # is checked against the deadlines independently.
        self._channels: Dict[str, list] = {}
        self._chan_seq = 0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._last_publish = None
        self._stale_peers: Dict[int, float] = {}
        self.reports = []   # crash-report paths written by this instance
        self.stalls = 0     # deadlines missed

    # -- heartbeats -----------------------------------------------------

    def beat(self, phase: Optional[str] = None) -> None:
        """Record liveness.  `phase` tags what the supervised thread is
        about to do; None keeps the current phase (pure refresh).  The
        most recent beater is the thread a ``raise``-policy stall
        targets."""
        now = self.clock()
        with self._lock:
            if phase is None:
                phase = self._last[0]
            self._last = (phase, now)
            self._count += 1
            self._timeline.append((phase, self._count, now,
                                   self.wall_clock()))
            self._thread_id = threading.get_ident()

    def channel(self, name: str, phase: str = "data") -> "_Channel":
        """Register an auxiliary supervised thread (e.g. the prefetch
        worker, utils/../dataset/prefetch.py) under its own heartbeat
        slot.  The channel's phase is watched against the same per-phase
        deadlines as the main slot, and a missed deadline async-raises
        the StallError into the CHANNEL's thread — which forwards it to
        the consumer (the prefetcher re-raises at ``next()``), landing in
        the retry loop exactly like a main-thread stall.  ``close()`` the
        returned handle when the worker retires, or its silence would
        read as a stall."""
        with self._lock:
            self._chan_seq += 1
            key = f"{name}#{self._chan_seq}"
            self._channels[key] = [phase, self.clock(), None, 0]
        return _Channel(self, key)

    def _beat_channel(self, key: str, phase: Optional[str]) -> None:
        now = self.clock()
        with self._lock:
            st = self._channels.get(key)
            if st is None:
                return
            st[0] = phase if phase is not None else st[0]
            st[1] = now
            st[2] = threading.get_ident()
            st[3] += 1

    def _close_channel(self, key: str) -> None:
        with self._lock:
            self._channels.pop(key, None)

    def deadline_for(self, phase: str) -> Optional[float]:
        if phase in self.deadlines:
            return self.deadlines[phase]
        root = phase.split(":", 1)[0]
        if root in self.deadlines:
            return self.deadlines[root]
        return self.default_deadline

    def set_deadlines(self, default: Optional[float] = None,
                      phases: Optional[Dict[str, float]] = None) -> None:
        """Reconfigure deadlines (a tool installs its stage limits here)."""
        if default is not None:
            self.default_deadline = default
        if phases:
            self.deadlines.update(phases)

    # -- lifecycle ------------------------------------------------------

    def start(self) -> "Supervisor":
        if self._thread is not None:
            return self
        self._stop.clear()
        with self._lock:  # a stale pre-start beat must not fire instantly
            self._last = (self._last[0], self.clock())
        if self.poll_interval is None:
            cands = [d for d in (*self.deadlines.values(),
                                 self.default_deadline) if d]
            if self.peer_lost > 0 and self.peer_dir and self.world > 1:
                # elastic detection must poll fast enough to notice a
                # publication-silent peer well inside the threshold
                cands.append(self.peer_lost)
            self.poll_interval = (min(max(min(cands) / 4.0, 0.05), 10.0)
                                  if cands else 1.0)
        self._thread = threading.Thread(target=self._monitor, daemon=True,
                                        name=self.name)
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout=10.0)
        self._thread = None
        if get_active() is self:
            set_active(None)

    def __enter__(self) -> "Supervisor":
        return self.start()

    def __exit__(self, *exc) -> bool:
        self.stop()
        return False

    # -- the monitor ----------------------------------------------------

    def _monitor(self) -> None:
        while not self._stop.wait(self.poll_interval):
            # each sub-duty individually guarded: a broken peer listing or
            # report write must not skip the deadline checks (or vice
            # versa) — the watchdog outlives any single failure
            try:
                self._publish_heartbeat()
            except Exception:  # noqa: BLE001
                self.heartbeat_errors += 1
                logger.warning("supervisor: heartbeat publish errored "
                               "(non-fatal, will retry)", exc_info=True)
            stale: Dict[int, float] = {}
            try:
                stale = self._check_peers(log=True)
            except Exception:  # noqa: BLE001
                logger.exception("supervisor peer check error (non-fatal)")
            try:
                self._check_elastic(stale)
            except Exception:  # noqa: BLE001
                logger.exception("supervisor elastic check error "
                                 "(non-fatal)")
            try:
                now = self.clock()
                # auxiliary channels first: a stalled input-pipeline
                # worker is the CAUSE of the main thread's stale data
                # wait, so its raise (forwarded through the prefetcher's
                # queue) should own the recovery
                chan_fired_phase = None
                with self._lock:
                    chans = [(k, st[0], st[1], st[2])
                             for k, st in self._channels.items()]
                for key, phase, t, tid in chans:
                    deadline = self.deadline_for(phase)
                    if not deadline or now - t <= deadline:
                        continue
                    if self._handle_stall(phase, now - t, deadline,
                                          channel=key, channel_tid=tid):
                        return
                    chan_fired_phase = phase
                with self._lock:
                    phase, t = self._last
                    if chan_fired_phase is not None and \
                            phase.split(":", 1)[0] == chan_fired_phase:
                        # the main slot's wait is downstream of the
                        # channel stall just handled — give it a full
                        # deadline of grace instead of double-raising
                        self._last = (phase, self.clock())
                        continue
                deadline = self.deadline_for(phase)
                if not deadline:
                    continue
                idle = self.clock() - t
                if idle <= deadline:
                    continue
                if self._handle_stall(phase, idle, deadline):
                    return
            except Exception:  # noqa: BLE001 — the watchdog must outlive
                # any single broken report write / peer listing
                logger.exception("supervisor monitor error (non-fatal)")

    def _handle_stall(self, phase: str, idle: float, deadline: float,
                      channel: Optional[str] = None,
                      channel_tid: Optional[int] = None) -> bool:
        """Deadline missed: report, then act per callback/policy.
        Returns True when monitoring should stop."""
        self.stalls += 1
        stale = self._check_peers(log=False)
        where = f"phase {phase!r}" if channel is None else \
            f"phase {phase!r} (worker channel {channel!r})"
        msg = (f"supervisor[{self.name}]: {where} made no progress "
               f"for {idle:.1f}s (deadline {deadline:.1f}s)")
        if stale:
            msg += "; stale peers: " + ", ".join(
                f"host {r} last seen {age:.0f}s ago"
                for r, age in sorted(stale.items()))
        report_path = self._write_report(phase, idle, deadline, stale, msg)
        logger.error("%s%s", msg,
                     f" (crash report: {report_path})" if report_path
                     else "")
        if self.on_stall is not None:
            stall = {"phase": phase, "idle_seconds": round(idle, 1),
                     "deadline_seconds": deadline, "report": report_path,
                     "stale_peers": stale, "message": msg}
            if channel is not None:
                stall["channel"] = channel
            self._reset_timer(phase, channel)  # grace before any re-fire
            return bool(self.on_stall(stall))
        if self.policy == "exit":
            # the supervised thread is presumed wedged in C (Python can't
            # unwind) — flush what we can and leave; the NEXT incarnation
            # recovers via the checkpoint lineage
            logger.error("supervisor: policy=exit — hard-exiting the "
                         "wedged process (crash report: %s)", report_path)
            try:
                for h in logger.handlers:
                    h.flush()
                sys.stderr.flush()
            except Exception:  # noqa: BLE001
                pass
            os._exit(86)
        # reset the timer so recovery (which beats no phases until it
        # re-enters the loop) gets a full deadline of grace before the
        # supervisor can declare a second stall
        self._reset_timer(phase, channel)
        with self._lock:
            tid = (channel_tid if channel_tid is not None
                   else self._thread_id)
        _LAST_STALL["message"] = msg
        if not _async_raise(tid, StallError):
            logger.error("supervisor: could not deliver StallError to "
                         "thread %s (already exited?)", tid)
        return False

    def _reset_timer(self, phase: str, channel: Optional[str]) -> None:
        with self._lock:
            if channel is None:
                self._last = (phase, self.clock())
            elif channel in self._channels:
                self._channels[channel][1] = self.clock()

    # -- crash report ---------------------------------------------------

    def crash_report(self, phase: str, idle: float, deadline: float,
                     stale: Optional[Dict[int, float]] = None,
                     reason: Optional[str] = None) -> dict:
        """The diagnostic dump: every thread's stack, the heartbeat
        timeline, chaos counters, platform info, stale peers."""
        now = self.clock()
        names = {t.ident: t.name for t in threading.enumerate()}
        threads = {}
        for tid, frame in sys._current_frames().items():
            label = f"{names.get(tid, '?')} (tid {tid})"
            threads[label] = [l.rstrip("\n")
                              for l in traceback.format_stack(frame)]
        with self._lock:
            timeline = [{"phase": p, "count": c,
                         "age_seconds": round(now - t, 3), "time": w}
                        for p, c, t, w in self._timeline]
            channels = {k: {"phase": st[0],
                            "age_seconds": round(now - st[1], 3),
                            "beats": st[3]}
                        for k, st in self._channels.items()}
        report = {"reason": reason or f"phase {phase!r} stalled",
                  "phase": phase,
                  "idle_seconds": round(idle, 3),
                  "deadline_seconds": deadline,
                  "time": self.wall_clock(),
                  "rank": self.rank, "world": self.world,
                  "timeline": timeline,
                  "channels": channels,
                  "threads": threads,
                  "chaos_counts": chaos.counts(),
                  "stale_peers": {str(r): round(a, 1)
                                  for r, a in (stale or {}).items()},
                  "platform": _platform_info()}
        # run telemetry (utils/telemetry): the recent span/event tail shows
        # what the run was DOING in the seconds before the hang — embedded
        # here so the diagnosis survives even if the trace file is lost
        from . import telemetry
        tracer = telemetry.get_active()
        if tracer is not None:
            report["trace_tail"] = tracer.events_tail(64)
        return report

    def _write_report(self, phase, idle, deadline, stale, msg):
        # flush-on-crash: the trace file on storage must include the
        # events leading into the stall, not just the last periodic flush
        from . import telemetry
        tracer = telemetry.get_active()
        if tracer is not None:
            try:
                tracer.instant("stall", cat="supervisor", phase=phase,
                               idle_seconds=round(idle, 1))
                tracer.flush()
            except Exception:  # noqa: BLE001 — telemetry is best-effort
                pass
        report = self.crash_report(phase, idle, deadline, stale, msg)
        data = json.dumps(report, indent=2, default=str).encode()
        if not self.report_dir:
            # no dir configured: the diagnostics still must not vanish
            logger.error("supervisor crash report (no report dir "
                         "configured):\n%s", data.decode(errors="replace"))
            return None
        from . import file_io
        base = file_io._strip_file_scheme(str(self.report_dir))
        path = file_io._join(
            base, f"crash_report-r{self.rank}-{self.stalls}.json")
        try:
            fs = file_io.get_filesystem(base)
            fs.makedirs(base)
            fs.write_bytes(path, data)
        except Exception as e:  # noqa: BLE001 — a broken report store must
            # not mask the stall itself
            logger.error("supervisor: crash report write to %s failed "
                         "(%s); dumping inline:\n%s", path, e,
                         data.decode(errors="replace"))
            return None
        # best-effort native-level dump beside the JSON (local dirs only:
        # faulthandler needs a real fd) — catches frames the pure-Python
        # walk cannot see
        try:
            import faulthandler
            if os.path.isdir(base):
                with open(path + ".stacks.txt", "w") as f:
                    faulthandler.dump_traceback(file=f, all_threads=True)
        except Exception:  # noqa: BLE001
            pass
        self.reports.append(path)
        return path

    # -- multi-host liveness --------------------------------------------

    def _heartbeat_path(self, rank: int) -> str:
        from . import file_io
        return file_io._join(file_io._strip_file_scheme(str(self.peer_dir)),
                             f"heartbeat.{rank}")

    def suspend_heartbeat(self) -> None:
        """Stop publishing liveness (the ``host.lost`` chaos drill:
        peers must see this rank go publication-silent)."""
        self._publish_suspended = True

    def resume_heartbeat(self) -> None:
        """Re-enable liveness publication — the JOINER path: a returning
        rank stays publication-silent until its announcement has cleaned
        the previous life's files and bumped the generation
        (parallel/elastic.announce_join), then resumes beating."""
        self._publish_suspended = False
        self._last_publish = None   # publish on the very next poll

    def hold_elastic(self) -> None:
        """Disable host-loss promotion until the next :meth:`reform` —
        the JOINER path: a rank gating on the cluster's checkpoint
        stream / awaiting admission is not yet a member and must not
        initiate a shrink of it (a transiently slow survivor heartbeat
        would otherwise read as a loss)."""
        self._peer_lost_pending = True

    def _publish_heartbeat(self) -> None:
        """Publish this process's last-beat wall time.  Runs on the
        MONITOR thread but stamps the SUPERVISED thread's last beat, so a
        stalled rank goes stale on its peers even while its monitor keeps
        publishing; the blob ALSO carries the monitor's own publication
        time (``published``) — the elastic host-LOST signal, which a
        merely-stalled or long-compiling rank keeps fresh.

        Best-effort with retry: a transient store failure is counted in
        ``heartbeat_errors`` and the publish re-attempted on the NEXT
        monitor poll (``_last_publish`` only advances on success) — one
        flake can delay a beat, never silently end liveness."""
        if not self.peer_dir or self.world <= 1 or self._publish_suspended:
            return
        now = self.clock()
        interval = self.publish_interval
        if interval is None:
            interval = max(self.peer_stale / 4.0, 0.5)
            if self.peer_lost > 0:
                # elastic-armed: publication age is the host-LOST signal,
                # so publishes must land well inside that threshold — the
                # 0.5s floor alone leaves no margin under a sub-second
                # peer_lost (a scheduling hiccup reads as a dead host)
                interval = min(interval, self.peer_lost / 4.0)
        if self._last_publish is not None and \
                now - self._last_publish < interval:
            return
        with self._lock:
            phase, _ = self._last
            count = self._count
            last_wall = (self._timeline[-1][3] if self._timeline
                         else self.wall_clock())
        blob = json.dumps({"rank": self.rank, "phase": phase,
                           "count": count, "time": last_wall,
                           "published": self.wall_clock(),
                           "generation": self.generation}).encode()
        path = self._heartbeat_path(self.rank)
        try:
            from . import file_io
            fs = file_io.get_filesystem(path)
            fs.makedirs(file_io._strip_file_scheme(str(self.peer_dir)))
            fs.write_bytes(path, blob)
        except Exception as e:  # noqa: BLE001 — liveness publication is
            # best-effort; a broken heartbeat store must not kill training
            self.heartbeat_errors += 1
            logger.warning("supervisor: heartbeat publish to %s failed "
                           "(%d so far; retrying next poll): %s",
                           path, self.heartbeat_errors, e)
            return
        self._last_publish = now

    def check_peers(self) -> Dict[int, float]:
        """rank -> seconds-since-last-beat for every peer whose heartbeat
        file is stale (public entry for tests/tools)."""
        return dict(self._check_peers(log=False))

    def stale_peers(self) -> Dict[int, float]:
        """The most recent peer-staleness observation (rank -> beat age,
        seconds) WITHOUT re-listing the store — the programmatic
        accessor beside the log line; refreshed every monitor poll."""
        with self._lock:
            return dict(self._stale_peers)

    def lost_peers(self) -> Dict[int, float]:
        """Peers whose heartbeat PUBLICATION is silent past the elastic
        ``peer_lost`` threshold (rank -> publication age, seconds) — the
        host-loss candidates, as of the last monitor poll."""
        with self._lock:
            return dict(self._lost_candidates)

    def _check_peers(self, log: bool) -> Dict[int, float]:
        # a world shrunk to 1 has no live peers to age-check, but lost
        # peers' frozen heartbeats must STAY watched: a returning rank
        # announces its next life there (parallel/elastic grow)
        if not self.peer_dir or (self.world <= 1 and not self._lost_peers):
            return {}
        from . import file_io
        base = file_io._strip_file_scheme(str(self.peer_dir))
        try:
            fs = file_io.get_filesystem(base)
            names = fs.listdir(base)
        except Exception:  # noqa: BLE001 — dir may not exist yet
            return {}
        now = self.wall_clock()
        stale = {}
        lost = {}
        for name in names:
            head, _, tail = name.rpartition(".")
            if head != "heartbeat" or not tail.isdigit():
                continue
            rank = int(tail)
            if rank == self.rank:
                continue
            if rank in self._lost_peers:
                # peers already recovered away from (elastic reform) keep
                # their final heartbeat file forever — not news, UNLESS a
                # HIGHER generation shows up: the rank's next life
                # announcing itself (parallel/elastic grow)
                if log:
                    self._check_returned(rank, fs)
                continue
            try:
                hb = json.loads(fs.read_bytes(self._heartbeat_path(rank)))
                age = now - float(hb["time"])
                # pre-elastic heartbeat blobs have no 'published' stamp:
                # fall back to the beat time (conservative — more lost)
                pub_age = now - float(hb.get("published", hb["time"]))
            except Exception:  # noqa: BLE001 — a torn heartbeat write is
                # transient; the next publish replaces it
                continue
            with self._lock:
                # remember each live peer's generation: on a loss it is
                # the baseline a RETURN must exceed
                self._peer_gens[rank] = int(hb.get("generation", 0))
            if self.peer_lost > 0 and pub_age > self.peer_lost:
                lost[rank] = pub_age
            if age > self.peer_stale:
                stale[rank] = age
                if log and rank not in self._stale_peers:
                    logger.warning(
                        "supervisor: peer host %d heartbeat is stale — "
                        "last seen %.0fs ago (phase %r); its collectives "
                        "will hang every rank", rank, age, hb.get("phase"))
                    if self.on_peer_stale is not None:
                        try:
                            self.on_peer_stale(rank, age)
                        except Exception:  # noqa: BLE001 — observer only
                            logger.exception("on_peer_stale callback "
                                             "failed (non-fatal)")
        if log and stale:
            # stragglers-about-to-die on the run timeline: one counter
            # sample per stale peer per poll (no-op when tracing is off)
            from . import telemetry
            telemetry.counter("peers", **{f"stale_age_r{r}": round(a, 3)
                                          for r, a in stale.items()})
        with self._lock:
            self._stale_peers = stale
            self._lost_candidates = lost
        return stale

    def _check_returned(self, rank: int, fs) -> None:
        """Detect a lost peer's RETURN: its heartbeat generation exceeds
        the one its previous life left behind.  Observation only (plus
        the once-per-episode ``on_peer_returned`` callback) — admission
        happens at the optimizer's next checkpoint boundary, never from
        the monitor thread."""
        try:
            hb = json.loads(fs.read_bytes(self._heartbeat_path(rank)))
            gen = int(hb.get("generation", 0))
        except Exception:  # noqa: BLE001 — torn write; next poll retries
            return
        with self._lock:
            if gen <= self._lost_peers.get(rank, 0) or \
                    rank in self._returned_peers:
                return
            self._returned_peers[rank] = gen
        logger.warning("supervisor: peer host %d RETURNED — heartbeat "
                       "generation %d supersedes its lost life; it can "
                       "be admitted at the next checkpoint boundary",
                       rank, gen)
        from . import telemetry
        telemetry.instant("elastic.peer_returned", cat="elastic",
                          rank=rank, generation=gen)
        if self.on_peer_returned is not None:
            try:
                self.on_peer_returned(rank, gen)
            except Exception:  # noqa: BLE001 — observer only
                logger.exception("on_peer_returned callback failed "
                                 "(non-fatal)")

    def returned_peers(self) -> Dict[int, int]:
        """Lost peers that have published a NEWER-generation heartbeat
        (rank -> generation) — returned hosts awaiting admission at the
        next checkpoint boundary; cleared by :meth:`reform`."""
        with self._lock:
            return dict(self._returned_peers)

    def peer_lost_pending(self) -> bool:
        """True between a host-loss promotion and the reform() that
        completes it — the window in which a join must be DEFERRED so
        shrink and grow re-forms never interleave."""
        return self._peer_lost_pending

    # -- elastic host-loss promotion (parallel/elastic) -----------------

    def _check_elastic(self, stale: Dict[int, float]) -> None:
        """Promote publication-silent peers into a typed PeerLostError
        (parallel/elastic step 1): stage the payload, publish the
        epoch-stamped ``elastic/recover.<rank>`` intent so slower ranks
        converge on their next poll, and async-raise into the supervised
        thread — the retry loop owns negotiate/re-form/resume."""
        if self.peer_lost <= 0 or self.world <= 1 or not self.peer_dir \
                or self._peer_lost_pending or not self.lineage_dir:
            return
        if self.clock() < self._promotion_grace_until:
            return  # post-reform rebuild window: observe, don't promote
        with self._lock:
            lost = {r: a for r, a in self._lost_candidates.items()
                    if r not in self._lost_peers}
        from ..parallel import elastic
        # fast convergence: another survivor already called this round
        intents = elastic.read_intents(
            self.lineage_dir, min_epoch=self.elastic_epoch + 1,
            exclude_rank=self.rank)
        for doc in intents.values():
            for r in doc.get("lost", []):
                if int(r) != self.rank and int(r) not in self._lost_peers:
                    lost.setdefault(int(r), 0.0)
        if not lost:
            return
        propose = max([self.elastic_epoch + 1] +
                      [int(d.get("epoch", 0)) for d in intents.values()])
        msg = (f"supervisor[{self.name}]: peer host(s) "
               f"{sorted(lost)} lost — heartbeat publication silent "
               f"{', '.join(f'{a:.0f}s (host {r})' for r, a in sorted(lost.items()))}"
               f"; starting elastic recovery round {propose}")
        try:
            elastic.publish_intent(self.lineage_dir, self.rank,
                                   propose, sorted(lost),
                                   self.wall_clock())
        except Exception:  # noqa: BLE001 — the local raise still recovers
            # this rank; peers fall back to their own thresholds
            logger.exception("supervisor: could not publish elastic "
                             "recovery intent (non-fatal)")
        from . import telemetry
        telemetry.instant("elastic.detect", cat="elastic",
                          lost=sorted(lost), epoch=propose)
        logger.error(msg)
        elastic.set_last_peer_lost(msg, sorted(lost), propose)
        self._peer_lost_pending = True
        with self._lock:
            tid = self._thread_id
        if not _async_raise(tid, elastic.PeerLostError):
            logger.error("supervisor: could not deliver PeerLostError to "
                         "thread %s (already exited?)", tid)

    def reform(self, rank: int, world: int, epoch: int,
               lost=(), returned=()) -> None:
        """Install the post-recovery topology (Optimizer._elastic_recover
        / _elastic_grow): the lost peers' frozen heartbeat files stop
        counting as news (each recorded with the generation its RETURN
        must exceed), `returned` ranks are re-admitted to the watch, the
        completed recovery round is recorded, and promotion re-arms for
        the NEXT loss."""
        with self._lock:
            self.rank, self.world = int(rank), int(world)
            for r in lost:
                self._lost_peers[int(r)] = self._peer_gens.get(int(r), 0)
            for r in returned:
                self._lost_peers.pop(int(r), None)
                self._returned_peers.pop(int(r), None)
            self._stale_peers = {r: a for r, a in self._stale_peers.items()
                                 if r not in self._lost_peers}
            self._lost_candidates = {
                r: a for r, a in self._lost_candidates.items()
                if r not in self._lost_peers}
        self.elastic_epoch = int(epoch)
        self._peer_lost_pending = False
        # every member recompiles against the new mesh now — hold the
        # next promotion until the rebuild window has passed
        self._promotion_grace_until = self.clock() + self.reform_grace
