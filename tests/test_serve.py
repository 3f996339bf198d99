"""Online inference serving (bigdl_tpu/serve): dynamic batching, replica
pool, deadline-aware load shedding, hot model swap.

The serving contract under test (docs/serving.md):
  - concurrent single requests coalesce into strictly fewer padded
    fixed-shape device batches, bit-identical to bulk Predictor.predict;
  - bounded queue -> typed ServerOverloaded at admission; per-request
    deadlines -> typed RequestTimeout at dequeue;
  - hot swap mid-traffic: zero dropped, zero misrouted requests;
  - chaos serve.batch faults surface as typed per-request errors;
  - a stalled replica trips its supervisor channel (crash report);
  - graceful shutdown leaks no threads.
"""

import glob
import json
import os
import threading
import time

import numpy as np
import jax
import pytest

import bigdl_tpu.nn as nn
from bigdl_tpu import Engine
from bigdl_tpu.optim import Predictor
from bigdl_tpu.serve import (DynamicBatcher, InferenceServer,
                             RequestTimeout, ServerClosed,
                             ServerOverloaded, default_buckets, pad_rows,
                             predict_in_fixed_batches)
from bigdl_tpu.utils import chaos
from bigdl_tpu.utils.supervisor import StallError, Supervisor


def _linear_model(seed=0, din=4, dout=3):
    return nn.Sequential().add(nn.Linear(din, dout)).build(
        jax.random.key(seed))


def _rows(n, din=4, seed=0):
    return np.random.default_rng(seed).normal(size=(n, din)) \
        .astype(np.float32)


# ---------------------------------------------------------------- batcher


def test_default_buckets_ladder():
    assert default_buckets(8) == (1, 2, 4, 8)
    assert default_buckets(12) == (1, 2, 4, 8, 12)
    assert default_buckets(1) == (1,)


def test_pad_rows_shared_padding():
    x = _rows(3)
    padded = pad_rows(x, 8)
    assert padded.shape == (8, 4)
    np.testing.assert_array_equal(padded[:3], x)
    np.testing.assert_array_equal(padded[3:], np.repeat(x[-1:], 5, axis=0))
    assert pad_rows(x, 3) is x  # full chunk untouched


def test_predict_in_fixed_batches_never_shows_new_shapes():
    """The shared bulk chunker: every forward sees exactly batch_size
    rows; outputs concatenate to the unpadded answer."""
    seen = []

    def forward(chunk):
        seen.append(len(chunk))
        return chunk * 2.0

    x = _rows(10)
    out = predict_in_fixed_batches(forward, x, 4)
    assert seen == [4, 4, 4]
    np.testing.assert_array_equal(out, x * 2.0)


def test_predict_in_fixed_batches_empty_input():
    """Zero-row feats never reach the forward and come back zero-row —
    the helper is public (__all__) and must be safe without the caller
    guarding the empty case first."""
    def forward(chunk):  # pragma: no cover — must not run
        raise AssertionError("forward called for empty feats")

    out = predict_in_fixed_batches(forward, _rows(0), 4)
    assert out.shape == (0, 4)


def test_batcher_deadline_shed_at_dequeue_counts():
    clock_box = [0.0]
    b = DynamicBatcher(max_batch=4, max_wait_s=0.0, queue_limit=8,
                       clock=lambda: clock_box[0])
    ok = b.submit(_rows(1)[0])
    late = b.submit(_rows(1)[0], deadline=5.0)
    clock_box[0] = 10.0  # both dequeue now; only `late` had a deadline
    live = b.collect()
    assert live == [ok]
    with pytest.raises(RequestTimeout):
        late.result(0)
    assert b.stats()["shed_timeout"] == 1


# ------------------------------------------------------------ acceptance


def test_coalescing_bit_identical_and_swap_mid_traffic(tmp_path):
    """Tier-1 acceptance: N concurrent single-sample requests are
    answered in strictly fewer than N device batches, bit-identical to
    per-sample Predictor.predict; a hot swap under sustained traffic
    completes with zero dropped and zero misrouted requests."""
    Engine.init()
    model_a = _linear_model(seed=0)
    model_b = _linear_model(seed=9)
    n = 32
    x = _rows(n)
    # per-sample bulk references for BOTH versions (bit-identity oracle)
    ref_a = np.stack([Predictor(model_a).predict(x[i:i + 1])[0]
                      for i in range(n)])
    ref_b = np.stack([Predictor(model_b).predict(x[i:i + 1])[0]
                      for i in range(n)])

    server = InferenceServer(model_a, max_batch=8, max_wait_ms=30,
                             queue_limit=2 * n, example=x[0]).start()
    results = {}
    lock = threading.Lock()

    def client(i):
        h = server.submit(x[i])
        with lock:
            results[i] = (h.result(30), h)

    # phase 1: pure coalescing on version 1
    threads = [threading.Thread(target=client, args=(i,))
               for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stats = server.stats()
    assert stats["batches"] < n, f"no coalescing: {stats}"
    assert stats["batch_rows"] == n
    for i in range(n):
        out, h = results[i]
        np.testing.assert_array_equal(out, ref_a[i])  # bit-identical
        assert h.version == 1

    # phase 2: hot swap during sustained traffic
    results.clear()
    stop_swap = threading.Event()

    def swapper():
        time.sleep(0.005)
        server.swap(model_b)
        stop_swap.set()

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(n)]
    sw = threading.Thread(target=swapper)
    sw.start()
    for t in threads:
        t.start()
        time.sleep(0.001)  # sustained trickle spanning the swap
    for t in threads:
        t.join()
    sw.join()
    stats = server.stats()
    assert stats["swaps"] == 1 and stats["version"] == 2
    assert len(results) == n  # zero dropped
    routed_new = 0
    for i in range(n):
        out, h = results[i]
        # zero misrouted: every answer is exactly one version's answer,
        # and the handle's version tag matches it
        if h.version == 2:
            np.testing.assert_array_equal(out, ref_b[i])
            routed_new += 1
        else:
            assert h.version == 1
            np.testing.assert_array_equal(out, ref_a[i])
    # after the swap the server answers only with the new version
    post = server.submit(x[0])
    np.testing.assert_array_equal(post.result(30), ref_b[0])
    assert post.version == 2
    server.stop()
    assert server.stats()["shed_overload"] == 0
    assert server.stats()["shed_timeout"] == 0


# ------------------------------------------------------------- shedding


def test_overload_typed_rejection_at_admission():
    Engine.init()
    server = InferenceServer(_linear_model(), max_batch=2, queue_limit=3)
    handles = [server.submit(_rows(1)[0]) for _ in range(3)]
    with pytest.raises(ServerOverloaded):
        server.submit(_rows(1)[0])
    assert server.stats()["shed_overload"] == 1
    server.start()  # the queued three still get answered
    for h in handles:
        assert h.result(30).shape == (3,)
    server.stop()


def test_deadline_timeout_typed_rejection():
    """Requests whose deadline passes while queued are shed with
    RequestTimeout and never reach the device."""
    Engine.init()
    server = InferenceServer(_linear_model(), max_batch=4, queue_limit=8)
    expired = [server.submit(_rows(1)[0], deadline_ms=1) for _ in range(3)]
    fresh = server.submit(_rows(1)[0])  # no deadline
    time.sleep(0.05)
    server.start()
    for h in expired:
        with pytest.raises(RequestTimeout):
            h.result(30)
    assert fresh.result(30).shape == (3,)
    stats = server.stats()
    assert stats["shed_timeout"] == 3
    assert stats["batch_rows"] == 1  # shed requests never hit the device
    server.stop()


def test_submit_shape_mismatch_typed_rejection():
    """A sample whose shape differs from the server's example is rejected
    typed at admission — it must never reach np.stack inside a coalesced
    batch where the failure would hit its batch-mates."""
    from bigdl_tpu.serve import ServeError

    Engine.init()
    with InferenceServer(_linear_model(), max_wait_ms=2,
                         example=_rows(1)[0]) as server:
        with pytest.raises(ServeError):
            server.submit(np.zeros((7,), np.float32))
        # the server keeps serving well-shaped traffic
        assert server.predict(_rows(1)[0], timeout=30).shape == (3,)


def test_stray_payload_fails_batch_typed_replica_survives():
    """A shape stray that defeats admission checks (here: enqueued via
    the batcher directly) fails ITS batch with a typed per-request error;
    the replica thread and the server survive."""
    Engine.init()
    server = InferenceServer(_linear_model(), max_batch=4, max_wait_ms=2,
                             example=_rows(1)[0])
    # both queued BEFORE start -> they coalesce into one batch
    good = server.batcher.submit(_rows(1)[0])
    bad = server.batcher.submit(np.zeros((7,), np.float32))
    server.start()
    with pytest.raises(ValueError):
        bad.result(30)
    with pytest.raises(ValueError):
        good.result(30)  # same batch: fails loudly, not a hang
    assert server.stats()["batch_errors"] == 1
    # the replica is still alive and answering
    assert server.predict(_rows(1)[0], timeout=30).shape == (3,)
    server.stop()


def test_graceful_drain_vs_hard_close():
    Engine.init()
    # graceful: queued requests are answered before workers exit
    server = InferenceServer(_linear_model(), queue_limit=8)
    hs = [server.submit(_rows(1)[0]) for _ in range(4)]
    server.start()
    server.stop(drain=True)
    for h in hs:
        assert h.result(1).shape == (3,)
    with pytest.raises(ServerClosed):
        server.submit(_rows(1)[0])
    # hard close: queued requests fail typed
    server = InferenceServer(_linear_model(), queue_limit=8)
    h = server.submit(_rows(1)[0])
    server.stop(drain=False)
    with pytest.raises(ServerClosed):
        h.result(1)


def test_shutdown_no_thread_leak():
    Engine.init()
    base = threading.active_count()
    server = InferenceServer(_linear_model(), replicas=3,
                             stall_seconds=5.0).start()
    assert server.predict(_rows(1)[0], timeout=30).shape == (3,)
    assert threading.active_count() > base
    server.stop()
    deadline = time.time() + 5
    while threading.active_count() > base and time.time() < deadline:
        time.sleep(0.01)
    assert threading.active_count() == base


# ------------------------------------------------------- chaos + stalls


def test_chaos_serve_batch_fault_is_typed_per_request():
    """An injected serve.batch fault fails exactly that batch's requests
    with the typed ChaosFault; the replica and later requests survive."""
    Engine.init()
    with chaos.scoped("serve.batch=fail@1"):
        with InferenceServer(_linear_model(), max_batch=4,
                             max_wait_ms=2) as server:
            h = server.submit(_rows(1)[0])
            with pytest.raises(chaos.ChaosFault):
                h.result(30)
            # the server is still serving
            assert server.predict(_rows(1)[0], timeout=30).shape == (3,)
            stats = server.stats()
            assert stats["batch_errors"] == 1 and stats["batches"] == 1


def test_chaos_serve_request_admission_fault():
    Engine.init()
    with chaos.scoped("serve.request=fail@2"):
        server = InferenceServer(_linear_model(), queue_limit=8)
        server.submit(_rows(1)[0])
        with pytest.raises(chaos.ChaosFault):
            server.submit(_rows(1)[0])
        server.stop(drain=False)


def test_stalled_replica_trips_supervisor_channel(tmp_path):
    """A replica wedged mid-batch (chaos stall) misses its 'serve'
    deadline: the supervisor writes a crash report naming the replica
    channel and async-raises StallError — the batch fails typed, the
    pool keeps serving."""
    Engine.init()
    sup = Supervisor({"serve": 0.3}, report_dir=str(tmp_path)).start()
    try:
        with chaos.scoped("serve.batch=stall*5@1"):
            with InferenceServer(_linear_model(), max_batch=4,
                                 max_wait_ms=2,
                                 supervisor=sup) as server:
                h = server.submit(_rows(1)[0])
                with pytest.raises(StallError):
                    h.result(30)
                reports = sorted(glob.glob(
                    os.path.join(str(tmp_path), "crash_report*.json")))
                assert reports, "supervisor wrote no crash report"
                with open(reports[0]) as f:
                    rep = json.load(f)
                assert rep["phase"] == "serve"
                assert any(k.startswith("serve-replica-0")
                           for k in rep["channels"]), rep["channels"]
                # the replica recovered: it still answers
                assert server.predict(_rows(1)[0],
                                      timeout=30).shape == (3,)
    finally:
        sup.stop()


# ------------------------------------------------------------- hot swap


def test_swap_from_checkpoint_lineage(tmp_path):
    """swap(dir) loads the NEWEST lineage snapshot through file_io
    (CRC-verified) and serves its params."""
    from bigdl_tpu.utils import file_io

    Engine.init()
    model = _linear_model(seed=0)
    new = _linear_model(seed=5)
    blob_np = jax.tree.map(np.asarray, new.params)
    # two snapshots: the newest (neval 7) must win
    file_io.save_checkpoint(str(tmp_path), 3,
                            {"params": jax.tree.map(np.asarray,
                                                    model.params),
                             "state": model.state}, {"method": {}})
    file_io.save_checkpoint(str(tmp_path), 7,
                            {"params": blob_np, "state": new.state},
                            {"method": {}})
    x = _rows(2)
    with InferenceServer(model, max_wait_ms=2, example=x[0]) as server:
        vid = server.swap(str(tmp_path))
        assert vid == 2
        assert "@7" in server.stats()["version_label"]
        out = server.predict(x[0], timeout=30)
        np.testing.assert_array_equal(out,
                                      Predictor(new).predict(x[:1])[0])


def test_swap_quantized_parity(tmp_path):
    """The swap path composes with quantize(): int8 replica answers agree
    with the float replica within the tolerance test_quantize.py pins for
    quantized logits (max abs < 0.15), and the int8 weights really are
    int8."""
    import jax.numpy as jnp

    from bigdl_tpu.models.lenet import LeNet5
    from bigdl_tpu.utils import file_io

    Engine.init()
    model = LeNet5(10).build(jax.random.key(0))
    file_io.save_checkpoint(
        str(tmp_path), 1,
        {"params": jax.tree.map(np.asarray, model.params),
         "state": model.state}, {"method": {}})
    x = np.random.default_rng(3).normal(size=(28, 28, 1)) \
        .astype(np.float32)
    with InferenceServer(model, max_wait_ms=2, example=x) as server:
        y_f = server.predict(x, timeout=60)
        server.swap(str(tmp_path), quantized=True)
        assert "+int8" in server.stats()["version_label"]
        q_leaves = jax.tree.leaves(server.version.module.params)
        assert any(l.dtype == jnp.int8 for l in q_leaves)
        y_q = server.predict(x, timeout=60)
    assert y_q.shape == y_f.shape
    assert float(np.max(np.abs(y_q - y_f))) < 0.15
    assert int(np.argmax(y_q)) == int(np.argmax(y_f))


def test_swap_build_does_not_block_data_path():
    """The slow half of swap() (checkpoint load / quantize / engine /
    warmup) must not hold the lock the replicas' stats updates take:
    while a swap is stuck in _load_module, predict() still answers."""
    Engine.init()
    x = _rows(2)
    with InferenceServer(_linear_model(seed=0), max_wait_ms=2,
                         example=x[0]) as server:
        gate = threading.Event()
        entered = threading.Event()
        orig = server._load_module

        def slow_load(source, state):
            entered.set()
            assert gate.wait(30), "test gate never opened"
            return orig(source, state)

        server._load_module = slow_load
        sw = threading.Thread(target=server.swap,
                              args=(_linear_model(seed=9),))
        sw.start()
        try:
            assert entered.wait(30)
            # swap is mid-build and holding its own lock — traffic and
            # stats() must proceed, not pause until the build finishes
            assert server.predict(x[0], timeout=30).shape == (3,)
            assert server.stats()["swaps"] == 0
        finally:
            gate.set()
            sw.join(30)
        assert server.stats()["swaps"] == 1
        assert server.stats()["version"] == 2


def test_swap_module_file(tmp_path):
    """swap() also accepts a Module.save file (bigdl_tpu-module-v1)."""
    Engine.init()
    new = _linear_model(seed=11)
    path = str(tmp_path / "model.bin")
    new.save(path)
    x = _rows(1)
    with InferenceServer(_linear_model(seed=0), max_wait_ms=2,
                         example=x[0]) as server:
        server.swap(path)
        np.testing.assert_array_equal(
            server.predict(x[0], timeout=30),
            Predictor(new).predict(x[:1])[0])


# ------------------------------------------------------ http front end


def test_http_front_end_roundtrip():
    """tools/serve_http.py: a real request path over the batcher —
    predict (single + batch), stats, health, typed error mapping."""
    import sys
    import urllib.error
    import urllib.request

    tools_dir = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools")
    if tools_dir not in sys.path:
        sys.path.insert(0, tools_dir)
    import serve_http

    Engine.init()
    model = _linear_model()
    server = InferenceServer(model, max_wait_ms=5,
                             example=np.zeros((4,), np.float32)).start()
    httpd = serve_http.serve_forever(server, "127.0.0.1", 0)
    port = httpd.server_address[1]
    base = f"http://127.0.0.1:{port}"

    def post(path, obj):
        req = urllib.request.Request(base + path,
                                     data=json.dumps(obj).encode(),
                                     method="POST")
        try:
            with urllib.request.urlopen(req, timeout=30) as r:
                return r.status, json.loads(r.read())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read())

    try:
        with urllib.request.urlopen(base + "/healthz", timeout=30) as r:
            assert json.loads(r.read())["ok"] is True
        x = _rows(3)
        status, body = post("/v1/predict", {"inputs": x[0].tolist()})
        assert status == 200
        np.testing.assert_allclose(
            np.asarray(body["outputs"], np.float32),
            Predictor(model).predict(x[:1])[0], rtol=1e-5)
        status, body = post("/v1/predict", {"inputs": x.tolist()})
        assert status == 200 and np.asarray(body["outputs"]).shape == (3, 3)
        status, body = post("/v1/predict", {})
        assert status == 400
        status, body = post("/v1/swap", {"source": "/does/not/exist"})
        assert status == 500 and "type" in body
        with urllib.request.urlopen(base + "/v1/stats", timeout=30) as r:
            stats = json.loads(r.read())
        assert stats["batches"] >= 2
    finally:
        httpd.shutdown()
        server.stop()


# ------------------------------------------- restart x AOT warm start


def test_replica_restart_rewarms_ladder_from_aot_cache(tmp_path,
                                                       monkeypatch):
    """A respawned replica re-warms its FULL bucket ladder through the
    AOT executable cache: the rebuilt engine performs zero fresh lowers,
    zero misses, zero XLA compiles (pure cache reads), asserted via the
    stats()["aot"] ledger — restart is seconds, not a cold compile.

    The XLA persistent cache is un-latched for the duration: an
    executable that XLA read back from its disk cache is not stored by the
    AOT layer (utils/aot._compile_timed), so with that cache warm the
    zero-fresh-lowers ledger would show misses."""
    from jax._src import compilation_cache as _cc

    from bigdl_tpu.utils import aot

    monkeypatch.setenv("BIGDL_TPU_AOT_CACHE", str(tmp_path / "aot"))
    aot.reset()
    prior_xla = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)
    _cc.reset_cache()
    try:
        Engine.init()
        x = _rows(4)
        with chaos.scoped("serve.replica@0=exit@1"):
            server = InferenceServer(_linear_model(), max_batch=8,
                                     max_wait_ms=2, queue_limit=32,
                                     example=x[0], replica_lost=0.3,
                                     restart_budget=3,
                                     restart_backoff=0.01).start()
            # startup warmup populated the cache (fresh lowers + stores)
            first = aot.stats()
            assert first["stores"] >= 1 and first["lowers"] >= 1
            # the exit drill kills replica 0 on its first batch; the
            # monitor respawns it on a FRESH engine whose warmup must be
            # pure cache reads
            out = server.predict(x[0], timeout=60)
            assert out.shape == (3,)
            deadline = time.time() + 10
            while server.stats()["restarts"] < 1 and \
                    time.time() < deadline:
                time.sleep(0.05)
            stats = server.stats()
            server.stop()
        assert stats["restarts"] == 1
        ledger = stats["aot"]
        assert ledger["lowers"] == first["lowers"], \
            "restart re-warm performed a fresh lower"
        assert ledger["misses"] == first["misses"], \
            "restart re-warm missed the cache"
        assert ledger["compiles"] == first["compiles"], \
            "restart re-warm compiled"
        assert ledger["hits"] > first["hits"]  # the ladder was cache reads
    finally:
        aot.reset()
        jax.config.update("jax_compilation_cache_dir", prior_xla)
        _cc.reset_cache()
