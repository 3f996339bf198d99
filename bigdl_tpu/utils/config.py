"""Process-level configuration tiers.

Reference: BigDL's three config tiers (SURVEY.md §5.6) — JVM system
properties `bigdl.*` (utils/Engine.scala:113-152, DistriOptimizer.scala:751),
the bundled spark-bigdl.conf, and per-app CLIs.  TPU re-design: the system
properties become `BIGDL_TPU_*` environment variables (the process-level
knob JAX programs use); the spark conf tier has no equivalent (no Spark);
CLIs live in models/run.py and tools/.

Every name the program reads has a row here and every row has a reader
(tests/test_config_table.py holds both, and pins the count of names).  A
row may hold several names: each after the first is written without the
`BIGDL_TPU` prefix, as in ` / _IO_BACKOFF_MAX`.

| env var                   | reference property               | default |
|---------------------------|----------------------------------|---------|
| BIGDL_TPU_SEED            | (RandomGenerator default seed)   | 0       |
| BIGDL_TPU_RETRY_TIMES     | bigdl.failure.retryTimes         | 5       |
| BIGDL_TPU_RETRY_INTERVAL  | bigdl.failure.retryTimeInterval  | 120     |
| BIGDL_TPU_NUM_THREADS     | bigdl.coreNumber / MKL threads   | ncpu    |
| BIGDL_TPU_LOG_FILE        | bigdl.utils.LoggerFilter.logFile | bigdl_tpu.log |
| BIGDL_TPU_DISABLE_LOGGER_FILTER | bigdl.utils.LoggerFilter.disable | 0 |
| BIGDL_TPU_PREEMPTION_CHECKPOINT | (net-new: SIGTERM -> final snapshot) | 1 |
| BIGDL_TPU_DEVICE_TIMEOUT  | (net-new: Engine.init device-discovery watchdog, seconds) | 0 (off) |
| BIGDL_TPU_RNN_HOIST_MAX_ELEMENTS | (net-new: ConvLSTM hoist cap) | 2^28 |
| BIGDL_TPU_XLA_CACHE | (net-new: persistent compile cache on/off; it lives at JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache — utils/platform.py) | 1 |
| BIGDL_TPU_CONV_PAD_MIN_CIN | (net-new: tiny-channel conv pad, nn/conv.py) | 8 |
| BIGDL_TPU_RING_ATTN | (net-new: MultiHeadAttention(seq_parallel=) takes the ring-attention path, nn/attention.py) | 0 (off) |
| BIGDL_TPU_NO_DONATE | (net-new: keep the train step's inputs alive instead of donating params/state/slots, optim/optimizer._build_step) | 0 (donate) |
| BIGDL_TPU_FSDP_MIN_SIZE | (net-new: leaves under this many elements stay replicated under fsdp, parallel/layout.py) | 4096 |
| BIGDL_TPU_PIPE_MICROBATCHES / _PIPE_SCHEDULE / _PIPE_VIRTUAL_STAGES | (net-new: pipeline microbatches, schedule gpipe or 1f1b, interleaved slices a device; parallel/pipeline.py) | 4 / gpipe / 1 |
| BIGDL_TPU_COORDINATOR / _NUM_PROCESSES / _PROCESS_ID | (net-new: jax.distributed address, world size and rank for Engine.init; a coordinator set means distributed) | off |
| BIGDL_TPU_TEST_INSTALLED | (net-new: suite resolves installed wheel) | off |
| BIGDL_TPU_IO_RETRIES | (net-new: remote-IO retry attempts per op, utils/file_io.py) | 3 |
| BIGDL_TPU_IO_BACKOFF_BASE / _IO_BACKOFF_MAX | (net-new: remote-IO backoff seconds, exponential + deterministic jitter) | 0.05 / 2.0 |
| BIGDL_TPU_IO_DEADLINE | (net-new: total seconds a retried remote op may take) | 60 |
| BIGDL_TPU_CKPT_KEEP_LAST | (net-new: checkpoint retention keep-last-K; 0 = unlimited) | 0 |
| BIGDL_TPU_CKPT_KEEP_EVERY_EPOCHS | (net-new: mark a keeper snapshot every N epochs) | 0 |
| BIGDL_TPU_CHAOS | (net-new: fault-injection spec, utils/chaos.py; see docs/robustness.md) | off |
| BIGDL_TPU_SUPERVISE_DATA / _SUPERVISE_STEP / _SUPERVISE_COMPILE / _SUPERVISE_CHECKPOINT / _SUPERVISE_VALIDATION / _SUPERVISE_SERVE | (net-new: per-phase stall deadlines, seconds; utils/supervisor.py — COMPILE covers each attempt's first step, which holds the XLA compile) | 0 (off) |
| BIGDL_TPU_SUPERVISE_DEADLINE | (net-new: default stall deadline for unlisted phases) | 0 (off) |
| BIGDL_TPU_SUPERVISE_POLICY | (net-new: stall response — raise StallError or hard-exit) | raise |
| BIGDL_TPU_SUPERVISE_PEER_STALE | (net-new: multi-host heartbeat staleness threshold, seconds) | 60 |
| BIGDL_TPU_DATA_SKIP_BUDGET | (net-new: corrupt records quarantined per data pass; utils/recordio.py) | 0 (fail loud) |
| BIGDL_TPU_PREFETCH_DEPTH | (net-new: background input-pipeline depth in batches, dataset/prefetch.py; 0 = synchronous path) | 2 |
| BIGDL_TPU_PREFETCH_STAGE | (net-new: stage the next batch onto devices from the prefetch worker — host->device double-buffering) | 1 single-process, 0 multi-host |
| BIGDL_TPU_TRACE | (net-new: run-telemetry trace output dir, utils/telemetry.py; empty = tracing off) | off |
| BIGDL_TPU_TRACE_RING | (net-new: max buffered trace events; oldest dropped beyond this) | 65536 |
| BIGDL_TPU_TRACE_FLUSH_EVERY | (net-new: trace events between automatic file flushes) | 4096 |
| BIGDL_TPU_COMPILE_CARDS | (net-new: compile cards, utils/hlostats.py: 1 keeps them in memory, a path also writes them there; cards also arm beside a trace dir) | off |
| BIGDL_TPU_METRICS / _METRICS_SLO_MS / _METRICS_WINDOW | (net-new: the /metrics plane on or off, the latency SLO it counts against, the rolling window of requests; utils/metrics_export.py) | 1 / 100 / 512 |
| BIGDL_TPU_SERVE_MAX_BATCH | (net-new: online serving — max requests coalesced per device batch, serve/) | 8 |
| BIGDL_TPU_SERVE_MAX_WAIT_MS | (net-new: flush deadline — max ms the oldest queued request waits for batch fill) | 5 |
| BIGDL_TPU_SERVE_QUEUE_LIMIT | (net-new: bounded request queue; admission past it raises ServerOverloaded) | 64 |
| BIGDL_TPU_SERVE_REPLICAS | (net-new: replica worker threads draining the shared serve queue) | 1 |
| BIGDL_TPU_SERVE_DEADLINE_MS | (net-new: default per-request deadline; expired queued requests shed with RequestTimeout; 0 = none) | 0 |
| BIGDL_TPU_SERVE_STALL_SECONDS | (net-new: per-replica supervision deadline — a wedged replica trips a stall + crash report; 0 = unwatched) | 0 |
| BIGDL_TPU_SERVE_REPLICA_LOST | (net-new: serving control plane, serve/control.py — seconds of replica heartbeat silence before the monitor condemns + restarts it; 0 = monitor off) | 0 (off) |
| BIGDL_TPU_SERVE_RESTART_BUDGET | (net-new: replica restarts allowed per replica slot before the server flips unhealthy on /healthz) | 3 |
| BIGDL_TPU_SERVE_RESTART_BACKOFF | (net-new: base seconds between replica restarts, doubling per consecutive restart) | 0.1 |
| BIGDL_TPU_SERVE_CANARY_MIN_BATCHES | (net-new: clean canary batches — and matching incumbent window — required before auto-promotion) | 8 |
| BIGDL_TPU_SERVE_CANARY_WINDOW | (net-new: rolling per-arm latency window, batches, for the canary p99 comparator) | 64 |
| BIGDL_TPU_SERVE_CANARY_LATENCY_RATIO | (net-new: auto-rollback when canary p99 latency exceeds ratio x the incumbent's) | 2.0 |
| BIGDL_TPU_SERVE_CANARY_ERROR_MARGIN | (net-new: auto-rollback when canary batch error rate exceeds the incumbent's + margin) | 0.05 |
| BIGDL_TPU_SERVE_TENANT_QPS | (net-new: per-tenant token-bucket admission quota, requests/s; over-quota -> typed QuotaExceeded with retry_after_s; 0 = quotas off) | 0 (off) |
| BIGDL_TPU_SERVE_TENANT_BURST | (net-new: per-tenant token-bucket depth; 0 = 2x qps, min 1) | 0 (auto) |
| BIGDL_TPU_SERVE_TRACE_LIMIT | (net-new: offered requests a TraceRecorder keeps, serve/tracefile.py) | 100000 |
| BIGDL_TPU_SERVE_AUTOSCALE_MIN / _SERVE_AUTOSCALE_MAX / _SERVE_AUTOSCALE_STEP | (net-new: replica bounds and replicas added a decision, serve/autoscale.py; max 0 = autoscaling off) | initial / 0 / 1 |
| BIGDL_TPU_SERVE_AUTOSCALE_TARGET_WAIT_MS / _SERVE_AUTOSCALE_UP_POLLS / _SERVE_AUTOSCALE_IDLE_S / _SERVE_AUTOSCALE_COOLDOWN_S / _SERVE_AUTOSCALE_POLL_S | (net-new: grow when the estimated queue wait passes the target for that many polls, shrink after idle seconds, cooldown between decisions, poll cadence) | 50 / 2 / 2.0 / 0.5 / 0.05 |
| BIGDL_TPU_DECODE_SLOTS / _DECODE_PAGE / _DECODE_MAX_LEN / _DECODE_QUEUE_LIMIT | (net-new: DecodeEngine in-flight slots, cache-length ladder step, cache cap (0 = the model's), bounded queue; serve/decode.py) | 4 / 128 / 0 / 64 |
| BIGDL_TPU_DECODE_DEADLINE_MS / _DECODE_MIN_STEP_MS / _DECODE_ADMISSION | (net-new: default request deadline (0 = none), per-tick pacing floor for drills, continuous or batch admission (batch is decode_smoke's baseline)) | 0 / 0 / continuous |
| BIGDL_TPU_AOT_CACHE | (net-new: AOT executable-cache dir, utils/aot.py — serialized compiled executables; warm start = cache read, zero XLA compiles; empty/0 = off) | off |
| BIGDL_TPU_AOT_CACHE_TAG | (net-new: free-form AOT fingerprint salt; bump to invalidate every entry at once) | "" |
| BIGDL_TPU_FUSED_UPDATE | (net-new: multi-tensor fused optimizer update, optim/fused.py — flatten grad/param/slot trees into dtype-homogeneous 1-D buffers; bit-identical to the per-leaf path) | 0 (off) |
| BIGDL_TPU_WIRE_BUCKET_MB | (net-new: max wire-dtype MB per gradient bucket, parallel/wire.py; 0 = per-leaf wire cast) | 0 (per-leaf) |
| BIGDL_TPU_CONV_ROUTE | (net-new: tiny-C_in conv lowering — pad (zero-pad), matmul (im2col reshaped-matmul, ops/convmm.py), lax (untouched); nn/conv._conv_route) | pad |
| BIGDL_TPU_ELASTIC_PEER_LOST | (net-new: elastic host-loss threshold, seconds of heartbeat-PUBLICATION silence promoting a peer to PeerLostError; parallel/elastic — 0 disarms elasticity) | 0 (off) |
| BIGDL_TPU_ELASTIC_WORLD / _ELASTIC_RANK | (net-new: simulated-multi-host logical topology for the elastic drill harness; utils/engine.Engine.world/rank) | off |
| BIGDL_TPU_ELASTIC_JOIN / _ELASTIC_JOIN_TIMEOUT / _ELASTIC_JOIN_POLL | (net-new: a starting rank joins a running job through Optimizer._elastic_join; seconds it waits for an offer / poll cadence; parallel/elastic.py) | 0 / 120 / 0.25 |
| BIGDL_TPU_ELASTIC_REFORM_GRACE | (net-new: seconds the supervisor lets a re-formed mesh settle before peer silence counts again) | 2.0 |
| BIGDL_TPU_ELASTIC_NEGOTIATE_TIMEOUT / _ELASTIC_NEGOTIATE_POLL | (net-new: seconds to wait for every survivor's lineage view / poll cadence during elastic negotiation) | 60 / 0.25 |
| BIGDL_TPU_DEPLOY_CANARY_FRACTION | (net-new: continuous deployment, serve/continuous.py — canary batch fraction the DeployController routes to each new release; 0 = plain full swaps) | 0.25 |
| BIGDL_TPU_DEPLOY_ROLLBACK_BUDGET | (net-new: consecutive canary rollbacks before the deploy controller freezes unhealthy instead of flapping) | 2 |
| BIGDL_TPU_DEPLOY_POLL_S | (net-new: release-lineage poll cadence, seconds; the watch itself backs off on the IO knobs when polled without one) | 0.25 |
| BIGDL_TPU_DEPLOY_DECISION_TIMEOUT | (net-new: seconds to wait a canary verdict out before freezing; 0 = wait forever) | 0 (off) |
| BIGDL_TPU_DEPLOY_MAX_UNAVAILABLE | (net-new: fleet mode — members concurrently in-swap during a rolling release fan-out; serve/fleetfront.py) | 1 |
| BIGDL_TPU_FLEET_MEMBER_LOST | (net-new: cross-process fleet, serve/fleet.py — seconds of member heartbeat-publication silence before the supervisor condemns + respawns it) | 5.0 |
| BIGDL_TPU_FLEET_RESTART_BUDGET | (net-new: respawns allowed per fleet member slot before it degrades to the survivors) | 3 |
| BIGDL_TPU_FLEET_RESTART_BACKOFF | (net-new: first member respawn delay, seconds, doubling per consecutive restart) | 0.5 |
| BIGDL_TPU_FLEET_POLL | (net-new: fleet supervisor monitor poll cadence, seconds) | 0.5 |
| BIGDL_TPU_FLEET_SPAWN_GRACE | (net-new: seconds a fresh worker spawn may take to publish its first heartbeat before silence counts) | 30.0 |
| BIGDL_TPU_FLEET_HEARTBEAT | (net-new: fleet worker beat interval, seconds; tools/serve_worker.py) | 0.5 |
| BIGDL_TPU_FLEET_KEEP_GENERATIONS | (net-new: member-record generations kept per index by the writer-side retention sweep) | 4 |
| BIGDL_TPU_FLEET_TIMEOUT_S | (net-new: fleet front tier, serve/fleetfront.py — per-member HTTP request timeout, seconds) | 60 |
| BIGDL_TPU_FLEET_RETRIES | (net-new: retry-on-next-member attempts after the first, idempotent predicts only) | 2 |
| BIGDL_TPU_FLEET_REFRESH_S | (net-new: fleet registry cache refresh interval, seconds) | 0.25 |
| BIGDL_TPU_FLEET_MAX_UNAVAILABLE | (net-new: front-tier default for members concurrently in-swap during a rolling deploy) | 1 |
| BIGDL_TPU_PROTOCOL_KEEP | (net-new: numbered protocol files — elastic grow offers — kept by the writer-side retention sweep, file_io.sweep_numbered) | 8 |
"""

from __future__ import annotations

import os

__all__ = ["get_int", "get_float", "get_bool", "get_str",
           "retry_times", "retry_time_interval", "num_threads", "seed"]


def get_str(name: str, default: str) -> str:
    return os.environ.get(f"BIGDL_TPU_{name}", default)


def get_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(f"BIGDL_TPU_{name}", default))
    except ValueError:
        return default


def get_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(f"BIGDL_TPU_{name}", default))
    except ValueError:
        return default


def get_bool(name: str, default: bool = False) -> bool:
    v = os.environ.get(f"BIGDL_TPU_{name}")
    if v is None:
        return default
    return v.strip().lower() in ("1", "true", "yes", "on")


def retry_times() -> int:
    """(reference: bigdl.failure.retryTimes, DistriOptimizer.scala:751)."""
    return get_int("RETRY_TIMES", 5)


def retry_time_interval() -> float:
    """Sliding window (seconds) that resets the retry counter
    (reference: bigdl.failure.retryTimeInterval, DistriOptimizer.scala:752)."""
    return get_float("RETRY_INTERVAL", 120.0)


def num_threads() -> int:
    return get_int("NUM_THREADS", os.cpu_count() or 1)


def seed() -> int:
    return get_int("SEED", 0)
