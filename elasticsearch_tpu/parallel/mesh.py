"""Device mesh construction for the search data plane.

Axes (the search-engine analog of an ML parallelism layout, SURVEY.md §2.10):
  * "shard"   — document partitions (data parallelism over the corpus);
                the index's stacked shard axis is sharded here.
  * "replica" — query-batch parallelism (replica groups serving QPS);
                the query batch is sharded here, the index is REPLICATED
                here — exactly the reference's "R copies per shard serve
                reads in parallel" (§2.10.2), but as a mesh axis instead
                of copied JVMs.

Cross-shard reduces (df psum, top-k all_gather) ride the "shard" axis —
on hardware these become ICI collectives; across pods XLA lowers them to
DCN automatically. The control plane (cluster state, doc transport) stays
host-side RPC, mirroring the reference's split (SURVEY.md §5.8).

Device ownership (ISSUE 19): each data node can OWN a disjoint device
subset (`node.devices` setting, or the harness's even split across
co-hosted nodes). A `DevicePool` carries that subset plus its OWN
dispatch lock, so collective programs from different nodes run
concurrently — the process-wide EXEC_LOCK remains only as the legacy
shared-pool fallback when no ownership is configured. The lock lives on
the POOL (not keyed by the raw device tuple) because two pools over
overlapping `devices[:need]` prefixes must never dispatch concurrently;
ownership resolution below only ever hands out disjoint subsets.
"""

from __future__ import annotations

import threading

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

SHARD_AXIS = "shard"
REPLICA_AXIS = "replica"
# the panel lane's one axis (search/aggs/panels.py): the chips a node owns,
# however many shards the index has
CHIP_AXIS = "chip"

# The legacy process-wide dispatch lock (PR-11): serializes shard_map
# programs that run on the SHARED pool (all of jax.devices()). Per-node
# DevicePools carry their own lock and never touch this one — that is
# what takes EXEC_LOCK off the per-node hot path. mesh_exec re-exports
# this as EXEC_LOCK for back-compat.
SHARED_EXEC_LOCK = threading.Lock()


class DevicePool:
    """A node's owned device subset + its private dispatch lock.

    `devkey` (the sorted tuple of device ids) feeds compiled-program
    cache keys so two nodes never share a program, and labels the
    device-stats registry so attribution survives concurrent per-node
    dispatch.
    """

    def __init__(self, devices, name: str = "pool", lock=None):
        self.devices = tuple(devices)
        self.name = str(name)
        self.devkey = tuple(int(d.id) for d in self.devices)
        self.lock = lock if lock is not None else threading.Lock()
        # (n_replicas, s_pad) -> Mesh over this pool's devices; guarded
        # separately from `lock` — mesh construction must not serialize
        # behind a long-running device program.
        self._meshes: dict = {}
        self._mesh_build_lock = threading.Lock()

    @property
    def is_shared(self) -> bool:
        return self.lock is SHARED_EXEC_LOCK

    def chip_mesh(self) -> Mesh:
        """Every chip of this pool on one axis, `CHIP_AXIS`: an axis of
        chips, not of shards, so S shards fit N chips for any S >= 1 and
        N >= 1 (5 over 4; 5 over 8, where three chips hold nothing and
        still take part in the collectives)."""
        with self._mesh_build_lock:
            mesh = self._meshes.get(CHIP_AXIS)
            if mesh is None:
                mesh = self._meshes[CHIP_AXIS] = Mesh(
                    np.asarray(self.devices), (CHIP_AXIS,))
        return mesh

    def home_of(self, shard: int) -> int:
        """The chip (a position in `devices`) that holds a shard's
        segments: shard number mod the chips owned (5 over 4: 0, 1, 2, 3,
        0), a pure function of what the node observes."""
        return shard % len(self.devices)

    def mesh_for(self, n_shards: int, n_replicas: int = 1):
        """Smallest (replicas x padded-shards) mesh over this pool that
        fits `n_shards`, or None if the pool is too small / trivial.
        Mirrors the legacy mesh_exec.mesh_for contract:
        returns (mesh, s_pad, n_replicas)."""
        n_dev = len(self.devices)
        if n_dev < 2 or n_shards < 1:
            return None
        per = n_dev // n_replicas
        if per < 1:
            return None
        s_pad = 1
        while s_pad < n_shards:
            s_pad *= 2
        if s_pad > per:
            return None
        key = (n_replicas, s_pad)
        with self._mesh_build_lock:
            mesh = self._meshes.get(key)
            if mesh is None:
                mesh = make_mesh(s_pad, n_replicas, devices=self.devices)
                self._meshes[key] = mesh
        return mesh, s_pad, n_replicas

    def __repr__(self):  # pragma: no cover - debug aid
        return f"DevicePool({self.name}, devices={self.devkey})"


_SHARED_POOL = None
_SHARED_POOL_LOCK = threading.Lock()


def shared_pool() -> DevicePool:
    """The legacy whole-process pool over jax.devices(), guarded by
    SHARED_EXEC_LOCK. Rebuilt if the device count changes (tests that
    fork with different XLA_FLAGS)."""
    global _SHARED_POOL
    devs = jax.devices()
    with _SHARED_POOL_LOCK:
        if _SHARED_POOL is None or len(_SHARED_POOL.devices) != len(devs):
            _SHARED_POOL = DevicePool(devs, name="shared",
                                      lock=SHARED_EXEC_LOCK)
        return _SHARED_POOL


def resolve_device_pool(settings) -> DevicePool | None:
    """Parse the `node.devices` setting into an owned DevicePool.

    Accepted forms:
      * explicit indices — ``"0,1,2,3"`` or a list of ints — picks those
        positions out of jax.devices();
      * ``"auto:<i>/<n>"`` — the i-th slice of an even n-way split (the
        harness's co-hosted-nodes form).

    Returns None (→ legacy shared pool + EXEC_LOCK) when the setting is
    absent, malformed, or the split leaves this node without devices.
    """
    if settings is None:
        return None
    try:
        spec = settings.get("node.devices")
    except Exception:
        return None
    if spec is None or spec == "":
        return None
    devs = jax.devices()
    own = None
    if isinstance(spec, str) and spec.startswith("auto:"):
        try:
            i_s, n_s = spec[5:].split("/")
            i, n = int(i_s), int(n_s)
        except ValueError:
            return None
        if n < 1 or not (0 <= i < n):
            return None
        per = len(devs) // n
        if per < 1:
            return None
        own = devs[i * per:(i + 1) * per]
    else:
        try:
            if isinstance(spec, str):
                ids = [int(x) for x in spec.split(",") if x.strip()]
            else:
                ids = [int(x) for x in spec]
            own = [devs[i] for i in ids if 0 <= i < len(devs)]
            if len(own) != len(ids):
                return None
        except (TypeError, ValueError):
            return None
    if not own:
        return None
    name = "devices[" + ",".join(str(int(d.id)) for d in own) + "]"
    return DevicePool(own, name=name)


_DISTRIBUTED_INITED = False


def maybe_init_distributed(settings) -> bool:
    """`jax.distributed.initialize` when `cluster.mesh.coordinator` is
    set — the multi-host data plane's entry point (ICI within a host,
    DCN between; SURVEY §5.8). Idempotent; failures are swallowed so a
    node without the coordinator reachable still serves on its local
    devices (the ladder declines, it never errors)."""
    global _DISTRIBUTED_INITED
    if settings is None:
        return False
    try:
        coord = settings.get("cluster.mesh.coordinator")
    except Exception:
        return False
    if not coord:
        return False
    if _DISTRIBUTED_INITED:
        return True
    try:
        jax.distributed.initialize(
            coordinator_address=str(coord),
            num_processes=int(settings.get("cluster.mesh.num_processes", 1)),
            process_id=int(settings.get("cluster.mesh.process_id", 0)))
        _DISTRIBUTED_INITED = True
        return True
    except Exception:
        return False


def make_mesh(n_shards: int | None = None, n_replicas: int = 1,
              devices=None) -> Mesh:
    devices = devices if devices is not None else jax.devices()
    if n_shards is None:
        n_shards = len(devices) // n_replicas
    need = n_shards * n_replicas
    if need > len(devices):
        raise ValueError(f"mesh {n_replicas}x{n_shards} needs {need} devices, "
                         f"have {len(devices)}")
    arr = np.asarray(devices[:need]).reshape(n_replicas, n_shards)
    return Mesh(arr, (REPLICA_AXIS, SHARD_AXIS))


def index_sharding(mesh: Mesh) -> NamedSharding:
    """Index tensors: leading shard axis split over "shard", replicated over
    "replica" (every replica group holds a full copy — the R-copies model)."""
    return NamedSharding(mesh, P(SHARD_AXIS))
