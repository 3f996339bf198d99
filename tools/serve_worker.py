#!/usr/bin/env python
"""One fleet member: an InferenceServer process that registers itself.

The thin wrapper ``serve/fleet.py`` supervises: build the model, start
the server + the stdlib HTTP front end (tools/serve_http.py's handler,
so the wire format is identical to a standalone server), publish a
CRC-framed member record + a liveness heartbeat into the shared fleet
dir, then beat until stopped, condemned, or killed.

Lifecycle (the member state machine docs/serving.md draws):

- **register**: bind HTTP first (``--port 0`` = ephemeral; the actual
  bound port goes into the record), warm the bucket ladder through the
  shared AOT cache (``BIGDL_TPU_AOT_CACHE`` — a respawn of a previously
  warmed fleet does ZERO fresh lowers, asserted by fleet_smoke via
  ``/v1/stats``'s aot ledger), then publish ``member.<idx>.<gen>``.
- **beat**: restamp ``heartbeats/heartbeat.<idx>`` every
  ``BIGDL_TPU_FLEET_HEARTBEAT`` seconds.  Each turn fires the
  ``fleet.member@<idx>`` chaos point (process-scoped: ``=exit@N`` dies
  instantly, ``=wedge@N`` blocks this loop uninterruptibly so the
  member goes publication-silent while its HTTP threads still answer —
  the zombie drill).
- **condemned**: the beat loop reads ``condemn.<idx>``; a generation at
  or below the condemned one drains gracefully and exits 0 — a zombie
  that wakes sees the supervisor's generation bump and leaves without
  fighting its replacement.
- **signalled**: SIGTERM/SIGINT drain in-flight requests
  (``stop(drain=True)``) before exit, so a rolling restart never drops
  accepted work.

Usage (normally spawned by fleet.FleetSupervisor, runnable by hand):
    python tools/serve_worker.py --fleet-dir /tmp/fleet --index 0 \
        --generation 1 --model linear [--platform tpu]

``--platform`` defaults to ``cpu``.  A chip belongs to one process, so a
fleet on chips needs one chip per worker; a worker whose device is
definitely not there (:func:`no_such_device`) exits
``fleet.EXIT_NO_DEVICE`` with the error on stderr and the supervisor
degrades its slot instead of respawning it.  Any other start-up error —
a chip that is busy, or still held by the dying predecessor — leaves
through the ordinary non-zero exit, and respawn with back-off gets its
chance.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import signal
import sys
import threading
import time

# runnable as `python tools/serve_worker.py` from the repo root
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)


_GOOGLE_PCI_VENDOR = "0x1ae0"  # every TPU chip is a PCI device of this vendor


def no_such_device(platform: str, err: Exception) -> bool:
    """True only where waiting cannot help: jax does not know `platform`,
    or a TPU was asked for on a host with no Google accelerator on its PCI
    bus.  A chip that another process holds, or that is still being handed
    over, is not that — its error is transient and is not judged here."""
    if "not in the list of known backends" in str(err):
        return True
    if platform == "tpu":
        vendors = glob.glob("/sys/bus/pci/devices/*/vendor")
        return not any(open(v).read().strip() == _GOOGLE_PCI_VENDOR
                       for v in vendors)
    return False


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--fleet-dir", required=True)
    ap.add_argument("--index", type=int, required=True)
    ap.add_argument("--generation", type=int, default=1)
    ap.add_argument("--model", default="linear", help="lenet|linear")
    ap.add_argument("--checkpoint", default=None,
                    help="initial weights (ckpt dir / snapshot file)")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0,
                    help="0 = ephemeral; the bound port is published "
                         "in the member record")
    ap.add_argument("--replicas", type=int, default=None)
    ap.add_argument("--max-batch", type=int, default=None)
    ap.add_argument("--heartbeat-s", type=float, default=None)
    ap.add_argument("--platform", default="cpu",
                    help="jax platform of this worker (default cpu).  "
                         "'tpu' takes the chip for this process alone: "
                         "one chip per worker, and a parent that never "
                         "touches jax")
    args = ap.parse_args(argv)

    import jax
    jax.config.update("jax_platforms", args.platform)

    from bigdl_tpu.serve import default_buckets, fleet
    from bigdl_tpu.serve.server import InferenceServer
    from bigdl_tpu.utils import chaos, config, telemetry
    from bigdl_tpu.utils.engine import Engine
    from tools.serve_http import build_model, serve_forever

    trace_dir = config.get_str("TRACE", "")
    tracer = None
    if trace_dir:
        # each member gets its own rank track in the merged timeline,
        # offset past the front tier's ranks
        tracer = telemetry.Tracer(trace_dir, rank=10 + args.index,
                                  flush_every=64)
        telemetry.set_active(tracer)
        telemetry.thread_name(f"fleet member {args.index}")

    try:
        Engine.init()
    except Exception as e:  # noqa: BLE001 — judged, or raised again
        if not no_such_device(args.platform, e):
            raise  # maybe transient: the supervisor's back-off handles it
        # a set-up fault, not a crash: the supervisor must not respawn
        # this with back-off as if it had died (fleet.EXIT_NO_DEVICE)
        print(f"serve_worker {args.index}: cannot get a "
              f"{args.platform!r} device: {type(e).__name__}: {e}",
              file=sys.stderr, flush=True)
        return fleet.EXIT_NO_DEVICE
    model, sample = build_model(args.model)
    server = InferenceServer(model, example=sample,
                             replicas=args.replicas,
                             max_batch=args.max_batch,
                             autoscale_max=0)
    server.start()
    server.warmup(sample)  # through the shared AOT cache: warm respawn
    if args.checkpoint:
        server.swap(args.checkpoint)

    httpd = serve_forever(server, args.host, args.port)
    port = httpd.server_address[1]

    fleet.publish_member(
        args.fleet_dir, index=args.index, generation=args.generation,
        pid=os.getpid(), port=port, host=args.host,
        devices=[str(d) for d in jax.devices()],
        buckets=default_buckets(server.max_batch),
        max_batch=server.max_batch)
    fleet.beat(args.fleet_dir, args.index, args.generation, 0)
    telemetry.instant("fleet.register", cat="fleet", index=args.index,
                      generation=args.generation, port=port)
    print(json.dumps({"member": args.index,
                      "generation": args.generation,
                      "pid": os.getpid(), "port": port}), flush=True)

    stop_ev = threading.Event()

    def _graceful(signum, frame):
        del frame
        stop_ev.set()

    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, _graceful)

    beat_s = (args.heartbeat_s if args.heartbeat_s is not None
              else config.get_float("FLEET_HEARTBEAT", 0.5))
    condemned = False
    count = 0
    while not stop_ev.is_set():
        count += 1
        # the drill hook: exit dies HERE (os._exit(117)); wedge blocks
        # HERE — the beat below never runs again and the supervisor sees
        # publication silence while HTTP threads keep answering (zombie)
        chaos.fire(f"fleet.member@{args.index}")
        if fleet.condemned_generation(args.fleet_dir,
                                      args.index) >= args.generation:
            condemned = True
            telemetry.instant("fleet.condemned_exit", cat="fleet",
                              index=args.index,
                              generation=args.generation)
            print(json.dumps({"member": args.index,
                              "generation": args.generation,
                              "condemned": True}), flush=True)
            break
        fleet.beat(args.fleet_dir, args.index, args.generation, count)
        stop_ev.wait(beat_s)

    # graceful either way: drain accepted requests before the sockets go
    httpd.shutdown()
    server.stop(drain=True)
    if tracer is not None:
        tracer.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
