"""Device-mesh construction — the L0 runtime floor.

TPU-native replacement for the reference's NCCL process-group / communicator
management (``BASELINE.json:5``: "CUDA/NCCL distributed trainer"). Instead of
per-strategy NCCL communicators, there is ONE ``jax.sharding.Mesh`` with named
axes; every parallelism strategy is expressed as a ``PartitionSpec`` over these
axes, and XLA lowers the resulting collectives onto ICI (intra-slice torus) or
DCN (cross-slice) depending on axis placement.

Axis conventions (outermost/slowest first — DCN-crossing axes must come first
so that their collectives ride DCN while everything else stays on ICI):

- ``dp``    pure data parallelism (gradient psum; params replicated)
- ``fsdp``  data parallelism with parameter/optimizer sharding (ZeRO-ish)
- ``pp``    pipeline stages
- ``tp``    tensor parallelism (Megatron-style column/row sharding)
- ``cp``    context/sequence parallelism (ring attention, Ulysses)
- ``ep``    expert parallelism (MoE)

A batch is sharded over ``('dp', 'fsdp')`` jointly; all other axes partition
model state or sequence dimensions.
"""

from __future__ import annotations

import dataclasses
import math
import warnings

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh

# Canonical axis order. DCN-crossing replicas (if any) split the leading dp
# axis, so dp stays outermost.
MESH_AXES: tuple[str, ...] = ("dp", "fsdp", "pp", "tp", "cp", "ep")

# Axes over which the global batch is sharded.
BATCH_AXES: tuple[str, ...] = ("dp", "fsdp")


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Sizes for each named mesh axis.

    Exactly one axis may be ``-1`` meaning "absorb all remaining devices".
    ``dcn_dp > 1`` declares that the leading ``dp`` axis spans that many
    TPU slices over DCN (hybrid mesh); within this single-host environment it
    simply changes device-order construction.
    """

    dp: int = -1
    fsdp: int = 1
    pp: int = 1
    tp: int = 1
    cp: int = 1
    ep: int = 1
    dcn_dp: int = 1

    def axis_sizes(self, n_devices: int) -> dict[str, int]:
        sizes = {a: getattr(self, a) for a in MESH_AXES}
        wild = [a for a, s in sizes.items() if s == -1]
        if len(wild) > 1:
            raise ValueError(f"at most one mesh axis may be -1, got {wild}")
        for a, s in sizes.items():
            if s < 1 and s != -1:
                raise ValueError(f"axis {a!r} has invalid size {s}")
        fixed = math.prod(s for s in sizes.values() if s != -1)
        if wild:
            if n_devices % fixed:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes product {fixed}"
                )
            sizes[wild[0]] = n_devices // fixed
        if math.prod(sizes.values()) != n_devices:
            raise ValueError(
                f"mesh {sizes} needs {math.prod(sizes.values())} devices, "
                f"have {n_devices}"
            )
        for a, s in sizes.items():
            if s < 1:
                raise ValueError(f"axis {a!r} resolved to invalid size {s}")
        return sizes


def build_mesh(
    config: MeshConfig | None = None, devices: list | None = None
) -> Mesh:
    """Build the global mesh.

    Uses ``mesh_utils.create_device_mesh`` so that, on a real TPU slice, mesh
    axes are laid out contiguously on the ICI torus (the TPU analogue of NCCL
    ring/tree topology autodetection). For ``dcn_dp > 1`` a hybrid mesh is
    built with the DCN factor outermost. Falls back to a plain reshape where
    topology info is unavailable (CPU simulation, single device).
    """
    config = config or MeshConfig()
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    sizes = config.axis_sizes(n)
    shape = tuple(sizes[a] for a in MESH_AXES)

    if config.dcn_dp > 1:
        if sizes["dp"] % config.dcn_dp:
            raise ValueError(
                f"dp={sizes['dp']} not divisible by dcn_dp={config.dcn_dp}"
            )
        ici_shape = (sizes["dp"] // config.dcn_dp,) + shape[1:]
        dcn_shape = (config.dcn_dp,) + (1,) * (len(MESH_AXES) - 1)
        try:
            arr = mesh_utils.create_hybrid_device_mesh(
                ici_shape, dcn_shape, devices=devices
            )
        except Exception as e:
            # The reshape fallback is ONLY sound on the CPU sim, where
            # enumeration order IS the simulated topology (dcn_dp groups
            # consecutive devices into slices — the member-numbering
            # contract comms_hier.HierTopology builds its replica groups
            # on). On real accelerators the hybrid builder failing means
            # slice metadata is missing/inconsistent; an enumeration-order
            # reshape would silently route intra-slice collectives over
            # DCN (and cross-slice ones over ICI), so refuse instead.
            if any(
                getattr(d, "platform", None) != "cpu" for d in devices
            ):
                raise RuntimeError(
                    "hybrid mesh construction failed on non-CPU devices "
                    f"(dcn_dp={config.dcn_dp}): an enumeration-order "
                    "reshape would mis-route hierarchical collectives "
                    "across the ICI/DCN boundary — fix the slice metadata "
                    "or set mesh.dcn_dp=1"
                ) from e
            _warn_topology_fallback(e)
            arr = np.asarray(devices).reshape(shape)
    else:
        try:
            arr = mesh_utils.create_device_mesh(
                shape, devices=devices, allow_split_physical_axes=True
            )
        except Exception as e:  # CPU sim / unusual topology
            _warn_topology_fallback(e)
            arr = np.asarray(devices).reshape(shape)
    return Mesh(arr, MESH_AXES)


def _warn_topology_fallback(e: Exception) -> None:
    # On real multi-chip hardware a fallback here silently loses ICI/DCN
    # contiguity (collectives may cross the wrong links) — make it loud.
    # On CPU sim / single device the fallback is expected and harmless.
    if any(d.platform != "cpu" for d in jax.devices()) and len(jax.devices()) > 1:
        warnings.warn(
            f"topology-aware mesh construction failed ({type(e).__name__}: {e}); "
            "falling back to enumeration-order reshape — collective performance "
            "may be degraded",
            RuntimeWarning,
            stacklevel=3,
        )


def init_distributed(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> bool:
    """Multi-host rendezvous — the reference's NCCL unique-id exchange.

    Thin wrapper over ``jax.distributed.initialize`` (one process per host,
    coordinator-based): explicit args win, else the standard env vars
    (``COORDINATOR_ADDRESS``/``NUM_PROCESSES``/``PROCESS_ID``, as used by
    jax itself) or cluster auto-detection. Returns True when a multi-process
    runtime was initialized, False for the single-process fast path. The
    coordinator doubles as the failure detector: a process that misses
    heartbeats is declared dead and the whole job exits for the restart-based
    recovery flow (SURVEY §5: relaunch + orbax resume).
    """
    import os

    coordinator_address = coordinator_address or os.environ.get(
        "COORDINATOR_ADDRESS"
    )
    if num_processes is None and "NUM_PROCESSES" in os.environ:
        num_processes = int(os.environ["NUM_PROCESSES"])
    if process_id is None and "PROCESS_ID" in os.environ:
        process_id = int(os.environ["PROCESS_ID"])
    if coordinator_address is None and num_processes in (None, 1):
        # No explicit config: fall through to jax's cluster auto-detection
        # (TPU pod metadata, SLURM, ...) when its markers are present —
        # otherwise a pod launch would silently train as N independent
        # single-process jobs. Plain single-host runs skip rendezvous.
        multi_host = (
            # >1 worker in the TPU pod metadata (a single name is not a
            # cluster).
            len(os.environ.get("TPU_WORKER_HOSTNAMES", "").split(",")) > 1
            or "MEGASCALE_COORDINATOR_ADDRESS" in os.environ
            or "SLURM_JOB_ID" in os.environ
            or "OMPI_COMM_WORLD_SIZE" in os.environ
        )
        if not multi_host:
            return False
        try:
            jax.distributed.initialize()  # cluster auto-detection
        except Exception as e:
            warnings.warn(
                f"multi-host markers present but cluster auto-detection "
                f"failed ({type(e).__name__}: {e}); continuing "
                "single-process — set COORDINATOR_ADDRESS/NUM_PROCESSES/"
                "PROCESS_ID explicitly for multi-host training",
                RuntimeWarning,
                stacklevel=2,
            )
            return False
        return True
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    return True


# Latency-hiding / async-collective XLA flags (SURVEY §2c "Overlap" row —
# the TPU counterpart of NCCL stream overlap). Public flags from the TPU
# scaling playbooks; exact availability varies by XLA build, so application
# is OPT-IN (config.train.xla_perf_flags) and happens via the environment
# BEFORE backend init — XLA rejects unknown flags loudly rather than
# silently ignoring them, which is the behavior we want when a build drifts.
XLA_PERF_FLAGS: tuple[str, ...] = (
    "--xla_tpu_enable_async_collective_fusion=true",
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true",
    "--xla_tpu_overlap_compute_collective_tc=true",
    "--xla_tpu_enable_async_collective_fusion_multiple_steps=true",
)


def apply_xla_perf_flags(
    flags: tuple[str, ...] = XLA_PERF_FLAGS, probe_timeout_s: int = 180
) -> str:
    """Append the perf flags to ``XLA_FLAGS`` (idempotent) IF this runtime
    accepts them. Must run before the first backend touch.

    Flag registries differ per PJRT plugin (``--xla_tpu_*`` only exists on
    TPU runtimes) and XLA ABORTS the process on unknown ``XLA_FLAGS`` —
    so acceptance is probed in a throwaway subprocess first; on rejection
    or probe timeout the environment is left untouched and a warning names
    the rejected set. Returns the final ``XLA_FLAGS`` value for logging."""
    import os
    import subprocess
    import sys

    current = os.environ.get("XLA_FLAGS", "")
    parts = current.split()
    for f in flags:
        name = f.split("=", 1)[0]
        if not any(p.split("=", 1)[0] == name for p in parts):
            parts.append(f)
    candidate = " ".join(parts)
    if candidate == current:
        return current

    env = dict(os.environ)
    env["XLA_FLAGS"] = candidate
    try:
        ok = (
            subprocess.run(
                [sys.executable, "-c",
                 "import jax; jax.jit(lambda x: x + 1)(1)"],
                env=env, capture_output=True, timeout=probe_timeout_s,
            ).returncode
            == 0
        )
    except subprocess.TimeoutExpired:
        ok = False
    if not ok:
        warnings.warn(
            f"this runtime rejected the XLA perf flags {flags}; leaving "
            "XLA_FLAGS unchanged",
            RuntimeWarning,
            stacklevel=2,
        )
        return current
    os.environ["XLA_FLAGS"] = candidate
    return candidate


def single_device_mesh(device=None) -> Mesh:
    """All-axes-size-1 mesh on one device (the unsharded baseline for parity
    tests and the single-chip path)."""
    if device is None:
        device = jax.devices()[0]
    arr = np.asarray([device]).reshape((1,) * len(MESH_AXES))
    return Mesh(arr, MESH_AXES)
