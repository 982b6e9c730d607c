"""Device mesh construction and multi-host rendezvous.

TPU-native communication backend replacing the reference's NCCL stack
(SURVEY.md §2.4, §5.8):

- ``torch.distributed.init_process_group('nccl', init_method=MASTER_ADDR)``
  (reference main.py:717-722) -> :func:`initialize_distributed`
  (``jax.distributed.initialize`` with a coordinator address).
- DDP gradient allreduce + SyncBN stat reduction -> XLA collectives inserted
  by GSPMD when computations cross the sharded ``data`` axis; explicit
  ``psum/pmean`` helpers live in :mod:`byol_tpu.parallel.collectives` for
  shard_map bodies.
- The process topology switch (reference main.py:786-814: mp.spawn vs
  1-proc-per-node) collapses to "one process per host, all devices visible";
  JAX owns device enumeration.

Mesh axes:
  ``data``     — data parallelism (the reference's only strategy);
  ``model``    — tensor parallelism, size 1 for BYOL parity, reserved so TP
                 can be enabled without re-plumbing (SURVEY.md §2.2);
  ``sequence`` — sequence/context parallelism for the ViT / ring-attention
                 path, size 1 by default.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from byol_tpu.observability import spans

DATA_AXIS = "data"
MODEL_AXIS = "model"
SEQUENCE_AXIS = "sequence"
AXIS_NAMES = (DATA_AXIS, SEQUENCE_AXIS, MODEL_AXIS)


_distributed_initialized = False


def initialize_distributed(coordinator_address: Optional[str] = None,
                           num_processes: Optional[int] = None,
                           process_id: Optional[int] = None) -> None:
    """Multi-host rendezvous; the ``--distributed-master``/``--distributed-rank``
    analog (reference main.py:105-109,794-797).  No-op for single process and
    idempotent, so the CLI can initialize early (before anything touches
    jax.devices()) and ``fit()`` can call it again safely."""
    global _distributed_initialized
    if coordinator_address and not _distributed_initialized:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id)
        _distributed_initialized = True


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    data: int = -1          # -1: all remaining devices
    sequence: int = 1
    model: int = 1
    # Number of ICI slices the data axis spans, data-parallel over DCN
    # (multi-slice / Megascale topologies).  1 = single slice (everything
    # rides ICI).  See :func:`build_mesh` for the layout contract.
    dcn_data: int = 1


def _slice_granules(devices: Sequence[jax.Device]) -> list:
    """Group devices into ICI islands ("granules"), DCN between them.

    On multi-slice TPU deployments each device carries a ``slice_index``;
    elsewhere (single slice, CPU) the process is the best available proxy
    for the ICI boundary.  Groups are ordered by key so every process
    builds the identical mesh."""
    # Namespaced keys: a slice id must never collide with a process id if a
    # device set ever mixes devices with and without slice_index.
    def key(d):
        s = getattr(d, "slice_index", None)
        return ("slice", s) if s is not None else ("proc", d.process_index)

    keys = sorted({key(d) for d in devices})
    by_key = {k: [] for k in keys}
    for d in devices:
        by_key[key(d)].append(d)
    return [by_key[k] for k in keys]


@spans.spanned("startup/mesh")
def build_mesh(spec: MeshSpec = MeshSpec(),
               devices: Optional[Sequence[jax.Device]] = None,
               dcn_granules: Optional[Sequence[Sequence[jax.Device]]] = None
               ) -> Mesh:
    """Build the (data, sequence, model) mesh.

    ``spec.dcn_data > 1`` requests the multi-slice layout (SURVEY.md §5.8:
    collectives ride ICI within a slice and DCN across slices — the
    reference's NCCL had the analogous NVLink-vs-IB hierarchy managed for
    it by the NCCL ring builder): the data axis is laid out SLICE-MAJOR
    (``data index = slice * per_slice_dp + position_within_slice``), with
    each slice's block containing only ICI-connected devices, so the
    backend decomposes a data-axis all-reduce into an in-slice ICI phase
    and a small cross-slice DCN phase.  The LOGICAL layout matches
    ``mesh_utils.create_hybrid_device_mesh([per_slice_dp, seq, model],
    dcn_mesh_shape=[dcn, 1, 1])`` with the two data factors merged into
    one named axis — merged so every P('data') annotation, collective,
    and FSDP rule in the framework works unchanged at multi-slice scale.
    Within a granule, devices keep raw enumeration order (create_device_mesh
    would additionally reorder for physical ICI topology; route granules
    through it on real multi-slice hardware if in-slice collective
    bandwidth profiles as a bottleneck).

    ``sequence``/``model`` axes never span slices (ring attention and TP
    collectives are latency-sensitive and must stay on ICI); this is
    enforced, not assumed.

    ``dcn_granules`` overrides slice discovery with an explicit grouping —
    tests use it to exercise the multi-slice layout on a CPU mesh where
    every device reports the same process.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    dp = spec.data
    if dp == -1:
        if n % (spec.sequence * spec.model) != 0:
            raise ValueError(
                f"{n} devices not divisible by sequence*model = "
                f"{spec.sequence * spec.model}")
        dp = n // (spec.sequence * spec.model)
    if dp * spec.sequence * spec.model != n:
        raise ValueError(
            f"mesh {dp}x{spec.sequence}x{spec.model} != {n} devices")
    if spec.dcn_data <= 1 and dcn_granules is None:
        arr = np.asarray(devices).reshape(dp, spec.sequence, spec.model)
        return Mesh(arr, AXIS_NAMES)

    granules = ([list(g) for g in dcn_granules] if dcn_granules is not None
                else _slice_granules(devices))
    n_slices = spec.dcn_data if spec.dcn_data > 1 else len(granules)
    if len(granules) != n_slices:
        raise ValueError(
            f"dcn_data={n_slices} but the devices form {len(granules)} "
            "ICI granules (slice/process groups)")
    if dp % n_slices != 0:
        raise ValueError(
            f"data={dp} not divisible by dcn_data={n_slices}")
    flat = [d for g in granules for d in g]
    if sorted(map(id, flat)) != sorted(map(id, devices)):
        raise ValueError(
            "dcn_granules must be disjoint and exactly cover the devices "
            f"argument: granules hold {len(flat)} devices "
            f"({len(set(map(id, flat)))} distinct) vs {len(devices)} given")
    per_slice = dp // n_slices * spec.sequence * spec.model
    blocks = []
    for g in granules:
        if len(g) != per_slice:
            raise ValueError(
                f"granule sizes {[len(x) for x in granules]} != "
                f"{per_slice} devices per slice "
                f"(data/dcn_data x sequence x model)")
        blocks.append(np.asarray(g).reshape(
            dp // n_slices, spec.sequence, spec.model))
    return Mesh(np.concatenate(blocks, axis=0), AXIS_NAMES)


def data_sharding(mesh: Mesh) -> NamedSharding:
    """Batch-dim sharded over the data axis; the DDP per-replica split analog
    (reference main.py:725 divides the global batch per rank)."""
    return NamedSharding(mesh, P(DATA_AXIS))

def batch_pspec() -> P:
    return P(DATA_AXIS)


def replicated(mesh: Mesh) -> NamedSharding:
    """Params/EMA/opt-state: replicated over every axis.  Replaces DDP's
    buffer broadcast (reference main.py:440-443, Quirk Q12) — under SPMD the
    replicas run identical programs, so replicated state stays bitwise
    consistent by construction."""
    return NamedSharding(mesh, P())


def shard_batch_to_mesh(batch, mesh: Mesh):
    """Place a host batch onto the mesh, batch dim over 'data'.

    Single process: a plain sharded device_put.  Multi-host: each process
    holds only ITS slice of the global batch (the loader's per-host shard,
    loader.py), so the global array is assembled with
    ``jax.make_array_from_process_local_data`` — device_put would demand
    the full global array on every host.  Works because both the loader's
    host sharding and the mesh's data axis order hosts by process index
    (contiguous rows ↔ contiguous devices)."""
    sh = data_sharding(mesh)
    if jax.process_count() == 1:
        return jax.tree_util.tree_map(
            lambda x: jax.device_put(x, sh), batch)

    # Global rows = local rows x (processes spanned by the DATA axis), NOT
    # x process_count: with e.g. multi-host TP (data=1, model=N) the batch
    # is replicated over hosts and the local array IS the global one.
    pid = jax.process_index()
    data_size = mesh.shape[DATA_AXIS]
    own = {i for i in range(data_size)
           if any(d.process_index == pid
                  for d in mesh.devices[i].flat)}
    if data_size % len(own) != 0:
        raise ValueError(
            f"data axis ({data_size}) unevenly split across processes: "
            f"this host owns indices {sorted(own)}")
    multiplier = data_size // len(own)

    def put(x):
        x = np.asarray(x)
        global_shape = (x.shape[0] * multiplier,) + x.shape[1:]
        return jax.make_array_from_process_local_data(sh, x, global_shape)

    return jax.tree_util.tree_map(put, batch)


def local_device_count(mesh: Mesh) -> int:
    return len([d for d in mesh.devices.flat
                if d.process_index == jax.process_index()])


def ambient_mesh() -> Optional[Mesh]:
    """The mesh entered via ``with mesh:`` (the physical mesh thread-local),
    or ``None``: what a module traced inside the jitted step can see of the
    devices it will run on."""
    from jax._src.mesh import thread_resources
    mesh = thread_resources.env.physical_mesh
    return None if mesh.empty else mesh
