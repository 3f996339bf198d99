"""Online inference serving: dynamic batching, replica pool, hot swap.

The training stack's online counterpart (ROADMAP north star: "serves
heavy traffic"): concurrent single requests are coalesced into padded
fixed-shape batches (serve/batcher.py) and drained by a pool of replica
worker threads running the same mesh-sharded forward as bulk
`Predictor.predict` (serve/server.py).  Bounded queue + per-request
deadlines give typed load shedding (`ServerOverloaded`,
`RequestTimeout`) instead of latency collapse; `swap()` hot-loads a new
checkpoint version (optionally int8-quantized) with zero dropped
requests.  The control plane (serve/control.py) makes the pool
self-healing: dead/silent replicas restart within a bounded budget,
`swap(canary_fraction=...)` auto-promotes or auto-rolls-back a canary
on a rolling p99/error comparison, and admission is tenant/priority
aware (token-bucket quotas, shed-lowest-priority-first).  The
scale-out layer makes the pool elastic and placement topology-aware:
a queue-wait-driven autoscaler (serve/autoscale.py) grows/shrinks the
pool between bounds with AOT-warm spawn, a `TopologyRouter`
(serve/router.py) places mesh-sharded replicas on disjoint device
subsets and routes by (bucket, per-replica queue depth), and recorded
request traces (serve/tracefile.py) replay at 10-100x
(`tools/scale_smoke.py`) reporting per-tenant SLO attainment.  The continuous
deployment layer (serve/continuous.py) closes the optimizer->canary
loop: the trainer's checkpoint path publishes CRC-framed release
entries and a `DeployController` watches the lineage, verifies each
entry, canaries it into the live server and promotes or rolls back on
the control plane's comparator — with a bounded consecutive-rollback
budget and a full model-version timeline (docs/continuous.md).  The
fleet layer (serve/fleet.py + serve/fleetfront.py) lifts the replica
state machine to OS PROCESSES: worker processes
(tools/serve_worker.py) register CRC-framed member records + liveness
heartbeats into a shared fleet dir (the same file_io plumbing elastic
training trusts), a `FleetSupervisor` condemns silent members by
generation bump and respawns them warm through the shared AOT cache
within a restart budget, and a `FleetFront` routes by (bucket, member
queue depth) over HTTP with bounded retry-on-next-member and rolling
`swap` fan-out for the DeployController's fleet mode.  The generative
layer (serve/decode.py) brings continuous-batching autoregressive
decode to the same stack: a `DecodeEngine` runs a persistent step loop
over fixed KV-cache slots (prefill/decode as separate AOT-cached
executables on a (slots, cache-page) bucket ladder), sequences join
and leave per step, and admission rides a per-sequence `DecodeQueue`
(deadline = time-to-last-token, tenant quotas, priority eviction).
See docs/serving.md.
"""

from .autoscale import AutoScaler
from .batcher import (DecodeQueue, DynamicBatcher, PendingRequest,
                      RequestTimeout, ServeError, ServerClosed,
                      ServerOverloaded, default_buckets, fit_bucket,
                      pad_rows, pad_tail, predict_in_fixed_batches)
from .decode import DecodeEngine, SlotFault, page_ladder
from .continuous import (DeployController, ReleasePublisher,
                         ReleaseRejected, read_release)
from .control import (CanaryController, CanaryRejected, QuotaExceeded,
                      ReplicaLostError, ReplicaMonitor, TenantQuotas)
from .fleet import FleetSupervisor, MemberLostError
from .fleetfront import FleetFront
from .router import PlacementError, TopologyRouter, plan_subsets
from .server import InferenceServer, ModelVersion
from .tracefile import (TraceEvent, TraceFormatError, TraceRecorder,
                        read_trace, replay, resolve_outcomes, slo_report,
                        write_trace)

__all__ = ["InferenceServer", "ModelVersion", "DynamicBatcher",
           "PendingRequest", "ServeError", "ServerOverloaded",
           "ServerClosed", "RequestTimeout", "ReplicaLostError",
           "CanaryRejected", "QuotaExceeded", "TenantQuotas",
           "CanaryController", "ReplicaMonitor", "default_buckets",
           "pad_rows", "pad_tail", "fit_bucket", "predict_in_fixed_batches",
           "AutoScaler", "TopologyRouter", "PlacementError",
           "plan_subsets", "TraceEvent", "TraceFormatError",
           "TraceRecorder", "read_trace", "write_trace", "replay",
           "resolve_outcomes", "slo_report",
           "DeployController", "ReleasePublisher", "ReleaseRejected",
           "read_release",
           "FleetSupervisor", "FleetFront", "MemberLostError",
           "DecodeEngine", "DecodeQueue", "SlotFault", "page_ladder"]
