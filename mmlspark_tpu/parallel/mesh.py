"""Device-mesh conventions — the communication backbone.

Replaces all three coordination planes of the reference (SURVEY.md §2.9):
the LightGBM driver TCP rendezvous + native ring (NetworkManager.scala),
the VW spanning-tree allreduce (VowpalWabbitClusterUtil.scala:15-43), and
Spark broadcast/collect/barrier — with a single `jax.sharding.Mesh` whose
axes carry XLA collectives over ICI (intra-slice) and DCN (inter-slice).

Axis conventions (used framework-wide):
  - ``dp``  — data parallel: rows sharded; histogram/gradient `psum`
              (LightGBM ``data_parallel``, VW allreduce, Horovod DP).
  - ``fp``  — feature parallel: feature dimension of histogram build
              sharded (LightGBM ``feature_parallel``).
  - ``mp``  — model parallel: reserved for tensor-parallel DNN paths.

The deterministic ring ordering the reference computes by sorting hosts on
min partition id (NetworkManager.scala:322-328) is inherent here: mesh
device order is deterministic, so no rendezvous is needed. The per-executor
"main worker election" (SharedState.scala:55-63) maps to
``process_index == 0`` / leader-by-mesh-coordinate.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence, Tuple

import numpy as np

DATA_AXIS = "dp"
FEATURE_AXIS = "fp"
MODEL_AXIS = "mp"
SEQUENCE_AXIS = "sp"


def data_axis() -> str:
    return DATA_AXIS


def feature_axis() -> str:
    return FEATURE_AXIS


def model_axis() -> str:
    return MODEL_AXIS


def sequence_axis() -> str:
    return SEQUENCE_AXIS


@dataclass
class MeshConfig:
    """Declarative mesh shape; -1 means "all remaining devices".

    ``sp`` is the sequence/context-parallel axis used by the
    long-context attention ops (:mod:`mmlspark_tpu.parallel.attention`);
    like the others it defaults to 1 so existing data-parallel programs
    are unchanged.
    """

    dp: int = -1
    fp: int = 1
    mp: int = 1
    sp: int = 1

    def resolve(self, num_devices: int) -> Tuple[int, int, int, int]:
        dp, fp, mp, sp = self.dp, self.fp, self.mp, self.sp
        fixed = max(fp, 1) * max(mp, 1) * max(sp, 1)
        if dp == -1:
            if num_devices % fixed:
                raise ValueError(
                    f"{num_devices} devices not divisible by "
                    f"fp*mp*sp={fixed}")
            dp = num_devices // fixed
        if dp * fp * mp * sp != num_devices:
            raise ValueError(
                f"mesh {dp}x{fp}x{mp}x{sp} != {num_devices} devices")
        return dp, fp, mp, sp


def create_mesh(config: Optional[MeshConfig] = None,
                devices: Optional[Sequence] = None,
                axis_names: Optional[Sequence[str]] = None):
    """Build a Mesh over all (or given) devices.

    Axes of size 1 are kept — collectives over singleton axes are no-ops,
    which lets the same shard_mapped program run from 1 chip to a pod.
    """
    import jax

    devices = list(devices if devices is not None else jax.devices())
    config = config or MeshConfig()
    dp, fp, mp, sp = config.resolve(len(devices))
    names = tuple(axis_names) if axis_names else (
        DATA_AXIS, FEATURE_AXIS, MODEL_AXIS, SEQUENCE_AXIS)
    shape = (dp, fp, mp, sp)
    if len(names) == 3:
        if sp != 1:
            raise ValueError("3 axis names require sp == 1")
        shape = (dp, fp, mp)
    elif len(names) != 4:
        raise ValueError(f"need 3 or 4 axis names, got {names}")
    dev_array = np.array(devices).reshape(shape)
    return jax.sharding.Mesh(dev_array, names)


def shrink_mesh(mesh, keep_dp: Optional[int] = None,
                lost_ranks: Sequence[int] = ()):
    """Re-form a mesh on a surviving slice of its ``dp`` axis.

    The elastic-recovery half of the resilience story: after a
    participant loss or an attributed stall, ``fit_resilient`` shrinks
    the data-parallel axis to the survivors and resumes from the last
    segment checkpoint. Either pass ``keep_dp`` (keep the first N dp
    coordinates) or ``lost_ranks`` (dp coordinates to drop). Returns
    the input mesh unchanged when nothing shrinks. The checkpoint
    fingerprint excludes the mesh, so segments fit before the shrink
    load cleanly on the re-formed mesh and the resumed fit is
    bitwise-identical to a deliberate elastic continuation with the
    same mesh schedule.
    """
    import jax

    if DATA_AXIS not in mesh.axis_names:
        raise ValueError(f"mesh has no '{DATA_AXIS}' axis: "
                         f"{mesh.axis_names}")
    di = list(mesh.axis_names).index(DATA_AXIS)
    dp = mesh.devices.shape[di]
    if lost_ranks:
        surviving = [r for r in range(dp) if r not in set(lost_ranks)]
    else:
        surviving = list(range(dp if keep_dp is None else keep_dp))
    if not surviving:
        raise ValueError("no surviving dp ranks to re-form the mesh on")
    if len(surviving) == dp:
        return mesh
    dev_array = np.take(mesh.devices, surviving, axis=di)
    return jax.sharding.Mesh(dev_array, mesh.axis_names)


def distributed_init(coordinator_address: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None,
                     local_device_ids: Optional[Sequence[int]] = None,
                     cpu_devices_per_process: Optional[int] = None,
                     **kwargs) -> None:
    """Join (or bootstrap) a multi-process JAX cluster.

    This is the rendezvous the reference implements by hand twice —
    the LightGBM driver opens a ServerSocket, collects every executor's
    ``ip:port``, sorts them into a deterministic ring and mails the
    roster back (NetworkManager.scala:59-84,322-328); VW builds a
    spanning tree the same way (VowpalWabbitClusterUtil.scala:15-43).
    On TPU both planes collapse into ``jax.distributed.initialize``:
    process 0 runs the coordinator service, every process registers,
    and afterwards ``jax.devices()`` is the *global* device list in a
    deterministic order, so ``create_mesh()`` spans hosts with no
    further ceremony and XLA lays collectives over ICI/DCN.

    All arguments default from the standard env vars
    (``JAX_COORDINATOR_ADDRESS``/``JAX_NUM_PROCESSES``/
    ``JAX_PROCESS_ID``) exactly as ``jax.distributed.initialize`` does,
    so launchers may pass either env or explicit values.

    ``cpu_devices_per_process``: when set, forces that many virtual CPU
    devices *before* the backend initializes — the offline multi-host
    test rig (N processes x M virtual CPU devices; collectives ride
    Gloo). Production TPU processes leave it ``None``.

    Extra keyword arguments pass through to
    ``jax.distributed.initialize`` (e.g. ``heartbeat_timeout_seconds``,
    which bounds how long survivors wait before a dead peer is
    detected and the process fail-fast terminates — the barrier
    failure-detection analog of the reference's socket-error
    propagation, pinned by
    tests/parallel/test_multihost.py::test_dead_rank_fails_fast).
    """
    import jax

    from mmlspark_tpu.core.faults import fault_point

    if cpu_devices_per_process is not None:
        from mmlspark_tpu.core.virtual_devices import force_cpu_devices
        force_cpu_devices(cpu_devices_per_process)
    _init_with_retries(
        lambda: jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
            local_device_ids=local_device_ids,
            **kwargs),
        fault_point)


def _init_with_retries(init_fn, fault_point) -> None:
    """Rendezvous with bounded retries: a coordinator that is still
    coming up (a restarted process 0, a slow container) must not kill
    every joiner permanently — the reference's executors likewise retry
    into the driver's ServerSocket. Attempts come from
    ``MMLSPARK_TPU_DIST_INIT_RETRIES`` (total tries, default 3);
    mis-use errors (double init, bad arguments) never retry."""
    from mmlspark_tpu.core.env import env_int
    from mmlspark_tpu.core.retries import RetryPolicy, with_retries
    from mmlspark_tpu.parallel.resilience import stall_guard

    def attempt():
        # MMLSPARK_TPU_WATCHDOG_INIT_S > 0 bounds each rendezvous
        # attempt — an init that never returns is a failure no retry
        # policy can see without this; a
        # TrainStalled attempt retries like any transient failure and
        # the exhaustion annotation says why the init gave up
        with stall_guard("distributed.init"):
            fault_point("distributed.init")
            init_fn()

    def should_retry(e: BaseException) -> bool:
        if isinstance(e, (ValueError, TypeError)):
            return False
        msg = str(e).lower()
        # "should only be called once" / "must be called before any
        # JAX computations": programming errors, not transient
        return "once" not in msg and "before any" not in msg

    tries = env_int("MMLSPARK_TPU_DIST_INIT_RETRIES", 3, minimum=1)
    with_retries(attempt,
                 policy=RetryPolicy(max_attempts=max(tries, 1),
                                    base_delay=1.0, max_delay=10.0),
                 should_retry=should_retry, describe="distributed.init")


def process_index() -> int:
    """This process's rank (the reference's main-worker election key,
    SharedState.scala:55-63: leader == process 0)."""
    import jax
    return jax.process_index()


def process_count() -> int:
    import jax
    return jax.process_count()


def is_multiprocess() -> bool:
    import jax
    return jax.process_count() > 1


_DEFAULT_MESH = None


def default_mesh():
    """Process-wide data-parallel mesh over all devices (cached)."""
    global _DEFAULT_MESH
    import jax
    if _DEFAULT_MESH is None or _DEFAULT_MESH.devices.size != len(jax.devices()):
        _DEFAULT_MESH = create_mesh()
    return _DEFAULT_MESH


def axis_size(mesh, axis: str) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape))[axis]


def replicated(mesh):
    import jax
    return jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())


def row_sharded(mesh, ndim: int = 1, axis: str = DATA_AXIS):
    import jax
    spec = [None] * ndim
    spec[0] = axis
    return jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(*spec))


def named_sharding(mesh, *spec):
    """NamedSharding from positional PartitionSpec entries — the
    train/prefetch loops build ad-hoc placements often enough that the
    two-class ceremony deserves one helper."""
    import jax
    return jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(*spec))
