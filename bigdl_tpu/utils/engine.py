"""Engine: device-topology discovery and execution configuration.

Reference: BigDL `utils/Engine.scala:36` — `Engine.init` (:93) discovers cluster
topology (node count x cores per node) from the Spark master URL
(`parseExecutorAndCore`, :353-418) and builds two thread pools (`Engine.default`,
`Engine.model`, :241-257) that all layers and the optimizer use.

TPU-native re-design: topology discovery is `jax.devices()` / `jax.process_count()`;
the "thread pools" collapse into XLA — a single compiled train step uses every core of
every chip it is sharded over.  `Engine.init()` builds the global `jax.sharding.Mesh`
that the rest of the framework (Optimizer, DataSet sharding, parallel strategies)
consumes.  Node-count-as-a-parameter is preserved: like BigDL's
`Engine.setNodeAndCore` trick that lets tests simulate an N-node cluster in one JVM
(utils/Engine.scala:313, used by DistriOptimizerSpec), `Engine.init(mesh_shape=...)`
can build any mesh over however many (possibly virtual CPU) devices exist.
"""

from __future__ import annotations

import logging
import os
from typing import Optional, Sequence

import numpy as np
import jax
from jax.sharding import Mesh

__all__ = ["Engine"]

logger = logging.getLogger("bigdl_tpu")


class Engine:
    """Process-wide singleton holding the device mesh (BigDL: utils/Engine.scala:36)."""

    _mesh: Optional[Mesh] = None
    _initialized = False
    #: outstanding device-discovery probe (thread, result box) after a
    #: timeout — reused by the next _discover_devices call (see there)
    _probe = None

    #: canonical mesh axis names, in order: data, pipeline(stage), tensor(model),
    #: sequence(context), expert
    DATA_AXIS = "data"
    PIPE_AXIS = "pipe"
    MODEL_AXIS = "model"
    SEQ_AXIS = "seq"
    EXPERT_AXIS = "expert"

    #: True once jax.distributed.initialize has run in this process
    _distributed_initialized = False

    #: elastic logical topology (parallel/elastic): None, or a dict
    #: {"rank": original rank id, "survivors": sorted tuple of surviving
    #: original rank ids}.  Ranks keep their ORIGINAL ids across shrinks
    #: (heartbeat/intent files stay addressable); the world SIZE and a
    #: rank's data-shard index derive from the survivor set.  Installed
    #: by reform(); the pre-fault logical topology of a simulated
    #: multi-host run comes from BIGDL_TPU_ELASTIC_WORLD/_ELASTIC_RANK.
    _elastic = None

    @classmethod
    def init_distributed(cls, coordinator_address: Optional[str] = None,
                         num_processes: Optional[int] = None,
                         process_id: Optional[int] = None,
                         local_device_ids: Optional[Sequence[int]] = None
                         ) -> None:
        """Join the multi-host runtime (jax.distributed.initialize).

        The reference discovers cluster topology from the Spark master URL
        (`Engine.parseExecutorAndCore`, utils/Engine.scala:353-418); here the
        coordination contract is environment variables — set by the launcher
        on every host, mirroring how spark-submit seeds each executor:

          BIGDL_TPU_COORDINATOR    host:port of process 0
          BIGDL_TPU_NUM_PROCESSES  world size
          BIGDL_TPU_PROCESS_ID     this process's rank

        On TPU pods all three may be omitted: jax auto-detects them from the
        TPU metadata service.  After this call `jax.devices()` is GLOBAL
        (every chip of every host) and `Engine.init()` builds the global mesh;
        each process addresses only its local chips and feeds them its data
        shard via `make_array_from_process_local_data`
        (Optimizer._put_batch — SURVEY.md §5.8).
        """
        if cls._distributed_initialized:
            return
        from . import config
        kwargs = {}
        coord = coordinator_address or config.get_str("COORDINATOR", "")
        if coord:
            kwargs["coordinator_address"] = coord
        nproc = (num_processes if num_processes is not None
                 else config.get_int("NUM_PROCESSES", 0))
        if nproc:
            kwargs["num_processes"] = int(nproc)
        pid = (process_id if process_id is not None
               else config.get_int("PROCESS_ID", -1))
        if pid >= 0:
            kwargs["process_id"] = int(pid)
        if local_device_ids is not None:
            kwargs["local_device_ids"] = list(local_device_ids)
        jax.distributed.initialize(**kwargs)
        cls._distributed_initialized = True
        logger.info(
            "Engine.init_distributed: process %d/%d, %d local / %d global "
            "devices", jax.process_index(), jax.process_count(),
            jax.local_device_count(), jax.device_count())

    @classmethod
    def init(cls, mesh_shape: Optional[dict] = None,
             devices: Optional[Sequence] = None,
             distributed: Optional[bool] = None) -> Mesh:
        """Discover devices and build the global mesh.

        mesh_shape: dict axis_name -> size, e.g. {"data": 4, "model": 2}.
          Defaults to pure data parallelism over every visible device — the
          reference's only inter-node strategy (SURVEY.md §2.5: sync data-parallel
          SGD is BigDL's sole distribution mode, optim/DistriOptimizer.scala).
        devices: explicit device list (tests pass virtual CPU devices here).
        distributed: join the multi-host runtime first (init_distributed).
          Defaults to True when BIGDL_TPU_COORDINATOR is set, so launcher
          scripts only need to export the env contract.
        """
        if distributed is None:
            from . import config
            distributed = bool(config.get_str("COORDINATOR", ""))
        if distributed:
            cls.init_distributed()
        devs = (list(devices) if devices is not None
                else cls._discover_devices())
        if mesh_shape is None:
            mesh_shape = {cls.DATA_AXIS: len(devs)}
        sizes = list(mesh_shape.values())
        total = int(np.prod(sizes))
        if total != len(devs):
            raise ValueError(
                f"mesh_shape {mesh_shape} needs {total} devices, have {len(devs)}")
        dev_array = np.array(devs).reshape(sizes)
        cls._mesh = Mesh(dev_array, tuple(mesh_shape.keys()))
        cls._initialized = True
        # the one call site of the persistent compile cache: every entry
        # point that runs a model comes through here first
        from . import config, native, platform
        platform.enable_compilation_cache()
        # host library: built from csrc/ on first use in a checkout that has
        # no (or a stale) binary; the pure-Python fallbacks serve when no
        # compiler is at hand.  Then the host-kernel thread count (reference:
        # Engine.init pins MKL threads via MKL.setNumThreads,
        # utils/Engine.scala:241-257)
        if not native.build():
            logger.info("Engine.init: native host library not built; "
                        "pure-Python host paths in use")
        native.set_num_threads(config.num_threads())
        logger.info("Engine.init: mesh %s over %d %s device(s)",
                    dict(zip(cls._mesh.axis_names, cls._mesh.devices.shape)),
                    len(devs), devs[0].platform)
        return cls._mesh

    @classmethod
    def _discover_devices(cls):
        """jax.devices() with an OPT-IN time limit: backend start-up can
        block for as long as another process holds the chip, or while a
        multi-host runtime waits for peers.  Set
        BIGDL_TPU_DEVICE_TIMEOUT=<seconds> to turn a silent hang into an
        actionable error.  Off by default: multi-host runs legitimately
        block in init until every process joins, and a default timeout
        would break that wait."""
        import os
        raw = os.environ.get("BIGDL_TPU_DEVICE_TIMEOUT")
        if raw is None or not raw.strip():
            return list(jax.devices())
        try:
            timeout = float(raw)
        except ValueError:
            # this knob exists to prevent a silent hang — silently
            # disabling it on a typo ('60s', '1m') would reproduce exactly
            # the failure it guards against
            raise ValueError(
                f"BIGDL_TPU_DEVICE_TIMEOUT={raw!r} is not a number of "
                "seconds (e.g. '60')") from None
        if timeout <= 0:
            return list(jax.devices())
        import threading
        # a timed-out probe thread cannot be killed (it is parked inside
        # native backend init) — but it must not be LEAKED once per call:
        # keep the outstanding (thread, box) and re-join it on the next
        # attempt, so at most one probe ever exists and a late-resolving
        # backend is still harvested instead of racing a second probe
        prior = cls._probe
        if prior is not None and prior[0].is_alive():
            t, box = prior
        else:
            box = {}

            def probe():
                try:
                    box["devices"] = list(jax.devices())
                except Exception as e:  # noqa: BLE001 — surfaced below
                    box["error"] = e

            t = threading.Thread(target=probe, daemon=True,
                                 name="bigdl-device-probe")
            t.start()
        t.join(timeout)
        if "devices" in box:
            cls._probe = None
            return box["devices"]
        if "error" in box:
            cls._probe = None
            raise box["error"]
        cls._probe = (t, box)
        raise TimeoutError(
            f"jax.devices() did not return within {timeout:.0f}s "
            "(BIGDL_TPU_DEVICE_TIMEOUT) — the accelerator backend did "
            "not start (is another process holding the chip?). Stop "
            "that process, or restart this one with JAX_PLATFORMS=cpu "
            "(the backend is already mid-init here, so an in-process "
            "jax.config update cannot take effect).")

    @classmethod
    def mesh(cls) -> Mesh:
        if cls._mesh is None:
            cls.init()
        return cls._mesh

    @classmethod
    def set_mesh(cls, mesh: Mesh) -> None:
        cls._mesh = mesh
        cls._initialized = True

    @classmethod
    def reset(cls) -> None:
        cls._mesh = None
        cls._initialized = False
        cls._probe = None
        cls._elastic = None

    # -- elastic topology (parallel/elastic) ----------------------------

    @classmethod
    def _env_elastic_world(cls) -> int:
        from . import config
        return config.get_int("ELASTIC_WORLD", 0)

    @classmethod
    def world(cls) -> int:
        """Logical world size: survivor count after a reform(), the
        BIGDL_TPU_ELASTIC_WORLD simulated topology, else
        jax.process_count() (the physical truth)."""
        if cls._elastic is not None:
            return len(cls._elastic["survivors"])
        w = cls._env_elastic_world()
        return w if w > 1 else jax.process_count()

    @classmethod
    def rank(cls) -> int:
        """This process's logical rank (ORIGINAL id — stable across
        shrinks); falls back to jax.process_index()."""
        if cls._elastic is not None:
            return cls._elastic["rank"]
        if cls._env_elastic_world() > 1:
            from . import config
            return config.get_int("ELASTIC_RANK", jax.process_index())
        return jax.process_index()

    @classmethod
    def survivors(cls) -> tuple:
        """Surviving original rank ids, sorted (all ranks pre-fault)."""
        if cls._elastic is not None:
            return cls._elastic["survivors"]
        return tuple(range(cls.world()))

    @classmethod
    def elastic_active(cls) -> bool:
        """True when a logical (elastic/simulated) topology overrides the
        physical jax process view."""
        return cls._elastic is not None or cls._env_elastic_world() > 1

    @classmethod
    def is_writer(cls) -> bool:
        """True on the rank that owns shared-store writes (checkpoints):
        the lowest surviving rank.  Identical to process_index()==0 until
        a reform() removes rank 0."""
        return cls.rank() == min(cls.survivors() or (0,))

    @classmethod
    def reform(cls, world: Optional[int] = None, rank: Optional[int] = None,
               survivors: Optional[Sequence[int]] = None,
               devices: Optional[Sequence] = None) -> Mesh:
        """Re-form the topology over a new rank set — SHRINK after a host
        loss (parallel/elastic step 3) or GROW when a returning host is
        admitted (step 4): the data axis resizes in either direction.

        `survivors` are ORIGINAL rank ids (default: the first `world`
        current survivors — a shrink-only shorthand; growing must name
        the widened set explicitly since ranks keep their original ids);
        `rank` is this process's original id (default: unchanged).  With
        `devices` given, the mesh itself is rebuilt over that device
        subset (the in-process simulated-host path: "losing a host" =
        losing its devices, "regaining" = its devices coming back); only
        1-D data-parallel meshes re-form this way — multi-axis layouts
        resize their data axis via :meth:`_reform_data_axis`.  Without
        `devices` the mesh keeps its current (local) devices and only
        the logical topology changes — the simulated-multi-host path,
        where each rank's devices were local all along.  The caller
        (Optimizer._elastic_recover / _elastic_grow) owns tearing down
        compiled steps and re-placing state."""
        cur = cls.survivors()
        if survivors is None:
            if world is None:
                raise ValueError("Engine.reform: need world or survivors")
            if int(world) > len(cur):
                raise ValueError(
                    f"Engine.reform: world={world} > current "
                    f"{len(cur)} — growing needs an explicit survivor "
                    "set (original rank ids cannot be invented)")
            survivors = cur[:int(world)]
        survivors = tuple(sorted(int(r) for r in survivors))
        if not survivors:
            raise ValueError("Engine.reform: empty survivor set")
        if world is not None and int(world) != len(survivors):
            raise ValueError(f"Engine.reform: world={world} disagrees with "
                             f"survivors {survivors}")
        if rank is None:
            rank = cls.rank()
        rank = int(rank)
        if rank not in survivors:
            raise ValueError(f"Engine.reform: rank {rank} not in survivors "
                             f"{survivors}")
        if devices is not None:
            devs = list(devices)
            if cls._mesh is not None and len(cls._mesh.axis_names) > 1:
                cls.set_mesh(cls._reform_data_axis(cls._mesh, devs))
            else:
                cls.set_mesh(Mesh(np.array(devs), (cls.DATA_AXIS,)))
        cls._elastic = {"rank": rank, "survivors": survivors}
        logger.warning("Engine.reform: world -> %d (rank %d, survivors %s)",
                       len(survivors), rank, list(survivors))
        return cls.mesh()

    @classmethod
    def _reform_data_axis(cls, mesh: Mesh, devs) -> Mesh:
        """Re-form a MULTI-AXIS mesh over a new device set by resizing
        the 'data' axis — in EITHER direction — and keeping every other
        axis (the fsdp x tp x pipe x expert block of a MeshLayout)
        intact.  When the device count is not a multiple of the non-data
        block — the shard groups cannot be preserved — this raises the
        typed MeshReformError instead of silently re-laying-out sharded
        parameters (parallel/layout; drilled by tests/test_layout.py and
        tests/test_elastic.py for the widen direction)."""
        from ..parallel.layout import MeshReformError
        names = tuple(mesh.axis_names)
        if cls.DATA_AXIS not in names:
            raise MeshReformError(
                f"cannot re-form mesh {dict(mesh.shape)} over "
                f"{len(devs)} device(s): no '{cls.DATA_AXIS}' "
                "axis to resize — rebuild the layout via Engine.init")
        sizes = [int(mesh.shape[a]) for a in names]
        di = names.index(cls.DATA_AXIS)
        block = int(np.prod([s for i, s in enumerate(sizes) if i != di]))
        if len(devs) < block or len(devs) % block:
            raise MeshReformError(
                f"cannot re-form mesh {dict(mesh.shape)} over "
                f"{len(devs)} device(s): the non-data block "
                f"({ {a: s for i, (a, s) in enumerate(zip(names, sizes)) if i != di} }"
                f" = {block} devices) must divide the device count to "
                "keep the fsdp/tp/pipe/expert shard groups intact; "
                f"re-form to a multiple of {block} devices or re-init a "
                "different layout")
        sizes[di] = len(devs) // block
        logger.warning("Engine.reform: mesh %s -> %s over %d device(s)",
                       dict(mesh.shape), dict(zip(names, sizes)), len(devs))
        return Mesh(np.array(devs).reshape(sizes), names)

    # kept as an alias: external drills/tests referenced the shrink name
    _shrink_data_axis = _reform_data_axis

    # -- topology accessors (BigDL: Engine.nodeNumber / Engine.coreNumber) --

    @classmethod
    def data_shard_info(cls, axis: str = None) -> tuple:
        """(shard_index, shard_count) for PER-PROCESS input sharding,
        derived from how the mesh's data axis maps onto processes (the
        locality role of ZippedPartitionsWithLocalityRDD, SURVEY.md §5.8).

        A process must feed exactly the batch rows its devices will hold:
        when the data axis spans processes, each process feeds its slice
        (shard_count > 1); when the data axis is intra-process (e.g. a
        'model'-first mesh where TP spans hosts and the batch is replicated
        across them), every process must feed the FULL batch
        (shard_count == 1).  Feeding a blind per-process slice in the
        latter layout silently trains each host on different data."""
        axis = axis or cls.DATA_AXIS
        if cls._elastic is not None or cls._env_elastic_world() > 1:
            # elastic logical topology (simulated multi-host / post-shrink):
            # each surviving rank feeds its index-th stride of the data
            surv = cls.survivors()
            return surv.index(cls.rank()), len(surv)
        if jax.process_count() == 1:
            return 0, 1
        mesh = cls.mesh()
        if axis not in mesh.axis_names:
            # no data axis -> batch_sharding replicates the batch: every
            # process must feed the identical full dataset
            return 0, 1
        devs = np.asarray(mesh.devices)
        ax = mesh.axis_names.index(axis)
        size = devs.shape[ax]
        rows = np.moveaxis(devs, ax, 0).reshape(size, -1)
        def coverage(pid):
            return tuple(i for i in range(size)
                         if any(d.process_index == pid for d in rows[i]))
        unique = sorted({coverage(p) for p in range(jax.process_count())})
        return unique.index(coverage(jax.process_index())), len(unique)

    @classmethod
    def node_number(cls) -> int:
        """Number of host processes (BigDL: Engine.nodeNumber, utils/Engine.scala)."""
        return jax.process_count()

    @classmethod
    def core_number(cls) -> int:
        """Devices attached to this process (BigDL: Engine.coreNumber)."""
        return jax.local_device_count()

    @classmethod
    def device_count(cls) -> int:
        return len(cls.mesh().devices.reshape(-1))

    @classmethod
    def data_parallel_size(cls) -> int:
        m = cls.mesh()
        return m.shape[cls.DATA_AXIS] if cls.DATA_AXIS in m.axis_names else 1
