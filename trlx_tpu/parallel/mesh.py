"""Device mesh construction.

The mesh has up to four axes — ("data", "fsdp", "tensor", "sequence") —
which together express every parallelism strategy the reference ships
(SURVEY.md §2.7): pure DP (Accelerate DDP), ZeRO-sharded DP (DeepSpeed →
"fsdp" axis), megatron TP ("tensor"), and sequence/context parallelism
("sequence", which the reference only has as Megatron SP inside a TP
group). Pipeline parallelism is handled separately via stage-sharded
`shard_map` (trlx_tpu/parallel/pipeline.py).

Batches are sharded over ("data", "fsdp") jointly — fsdp is just DP that
additionally shards params/optimizer state — so global batch = per-shard
batch x data x fsdp.
"""

from dataclasses import dataclass
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from trlx_tpu.utils import logging

logger = logging.get_logger(__name__)

MESH_AXES = ("data", "fsdp", "tensor", "sequence")


def _resolve_axis_sizes(n_devices: int, sizes: Sequence[int]) -> Tuple[int, ...]:
    """Resolve -1 entries to soak up remaining devices (at most one -1)."""
    sizes = list(sizes)
    known = 1
    unknown = []
    for i, s in enumerate(sizes):
        if s == -1:
            unknown.append(i)
        else:
            known *= s
    if len(unknown) > 1:
        raise ValueError(f"At most one mesh axis may be -1, got {sizes}")
    if unknown:
        if n_devices % known != 0:
            raise ValueError(f"{n_devices} devices not divisible by fixed axes {sizes}")
        sizes[unknown[0]] = n_devices // known
    total = int(np.prod(sizes))
    if total != n_devices:
        raise ValueError(
            f"Mesh axes {dict(zip(MESH_AXES, sizes))} use {total} devices, "
            f"but {n_devices} are available"
        )
    return tuple(sizes)


def make_mesh(
    data: int = -1,
    fsdp: int = 1,
    tensor: int = 1,
    sequence: int = 1,
    dcn_data: int = 1,
    devices=None,
) -> Mesh:
    """Build the global device mesh.

    Device order matters for ICI locality: `mesh_utils.create_device_mesh`
    lays axes out so the innermost (tensor/sequence) axes map to
    nearest-neighbor ICI links, keeping TP all-reduces and ring-attention
    ppermutes off DCN.

    `dcn_data > 1` builds a multi-slice hybrid mesh: `dcn_data` slices are
    data-parallel over DCN while fsdp/tensor/sequence (and the per-slice
    share of `data`) stay within each slice's ICI. This is the multi-slice
    scale-out path the reference reaches through NCCL over IB + slurm
    (SURVEY.md §5.8); here the slow-network axis folds into the leading
    "data" axis so only gradient psums cross DCN.
    """
    if dcn_data < 1:
        # unlike the ICI axes there is no -1 wildcard here: the slice count
        # is fixed by the deployment, never inferred
        raise ValueError(f"dcn_data must be >= 1, got {dcn_data}")
    devices = devices if devices is not None else jax.devices()
    sizes = _resolve_axis_sizes(len(devices), [data, fsdp, tensor, sequence])
    if dcn_data > 1 and sizes[0] % dcn_data != 0:
        raise ValueError(f"data axis {sizes[0]} not divisible by dcn_data={dcn_data}")

    has_slice_topology = getattr(devices[0], "slice_index", None) is not None
    if dcn_data > 1 and not has_slice_topology:
        logger.warning(
            f"dcn_data={dcn_data} requested but devices expose no slice "
            "topology (CPU test mesh, or a platform without slice_index): "
            "falling back to a flat device mesh. On a real multi-slice "
            "deployment this would put inner mesh axes on the slow network."
        )
    if dcn_data > 1 and has_slice_topology:
        # Real multi-slice topology: let layout errors propagate — a silent
        # fallback here could put TP/FSDP axes on DCN, defeating the point.
        from jax.experimental import mesh_utils

        dev_array = mesh_utils.create_hybrid_device_mesh(
            (sizes[0] // dcn_data,) + tuple(sizes[1:]), (dcn_data, 1, 1, 1),
            devices=devices,
        )
    elif devices[0].platform == "tpu":
        # Let layout errors propagate: a flat reshape here would put
        # tensor/sequence axes on whatever links the enumeration order
        # happens to give, in silence.
        from jax.experimental import mesh_utils

        dev_array = mesh_utils.create_device_mesh(sizes, devices=devices)
    else:
        # CPU test meshes carry no topology to lay axes out over
        dev_array = np.asarray(devices).reshape(sizes)
    return Mesh(dev_array, MESH_AXES)


# Primary double-init guard: set after a successful bootstrap in THIS module
# so re-entry (e.g. a second trlx.train() in one process) no-ops without
# depending on jax private state or error-message wording.
_distributed_initialized = False


def initialize_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Initialize JAX's multi-host runtime (the reference's
    `torch.distributed.init_process_group` + Accelerate launcher role,
    SURVEY.md §5.8). On TPU pods `jax.distributed.initialize()` discovers
    the topology from metadata; args/env (`COORDINATOR_ADDRESS`,
    `NUM_PROCESSES`, `PROCESS_ID` — the WORLD_SIZE/RANK analogues of
    §5.6) override for CPU/GPU fleets. No-op when single-process or
    already initialized."""
    import os

    global _distributed_initialized
    if _distributed_initialized:
        logger.info("jax.distributed already initialized; skipping")
        return

    coordinator_address = coordinator_address or os.environ.get("COORDINATOR_ADDRESS")
    if num_processes is None and "NUM_PROCESSES" in os.environ:
        num_processes = int(os.environ["NUM_PROCESSES"])
    if process_id is None and "PROCESS_ID" in os.environ:
        process_id = int(os.environ["PROCESS_ID"])
    # TPU pods carry worker metadata in the environment; there,
    # jax.distributed.initialize() auto-discovers the topology with no args.
    # Require >1 worker hostname — single-host setups also export
    # TPU_WORKER_HOSTNAMES.
    on_tpu_pod = (
        "," in os.environ.get("TPU_WORKER_HOSTNAMES", "")
        or "MEGASCALE_COORDINATOR_ADDRESS" in os.environ
    )
    if coordinator_address is None and num_processes in (None, 1) and not on_tpu_pod:
        if process_id is not None:
            raise ValueError(
                f"process_id={process_id} given without coordinator_address/"
                "num_processes — refusing to silently run single-process"
            )
        return  # single-process: nothing to initialize
    if jax.distributed.is_initialized():
        logger.info("jax.distributed already initialized; skipping")
        _distributed_initialized = True
        return
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    _distributed_initialized = True


@dataclass
class MeshRuntime:
    """Holds the mesh plus convenience shardings; the single object trainers
    use for device placement (the counterpart of the reference's
    `Accelerator` + apex `parallel_state`, SURVEY.md §5.8)."""

    mesh: Mesh

    @classmethod
    def from_config(cls, parallel_config, devices=None) -> "MeshRuntime":
        # Multi-host bootstrap before the first jax.devices() call: no-op on
        # single-process setups, auto-discovers TPU pod topology otherwise.
        if devices is None:
            initialize_distributed()
        if getattr(parallel_config, "pipeline", 1) not in (1, None):
            # ("data", "pipe", "fsdp", "tensor") mesh for GPipe trainers:
            # data/pipe are the manual shard_map axes; fsdp/tensor stay
            # GSPMD-auto inside the pipeline program (TP x PP / ZeRO x PP,
            # the reference's megatron_65b.yaml:49-50 TP=8 x PP=4 layout).
            if getattr(parallel_config, "dcn_data", 1) != 1:
                raise NotImplementedError(
                    "parallel.pipeline composes with data/fsdp/tensor/"
                    "sequence; set dcn_data to 1"
                )
            from trlx_tpu.parallel.pipeline import make_pipe_mesh

            devices = devices if devices is not None else jax.devices()
            pipe = parallel_config.pipeline
            tensor = parallel_config.tensor
            fsdp = parallel_config.fsdp
            sequence = parallel_config.sequence
            if tensor < 1 or fsdp < 1 or pipe < 1 or sequence < 1:
                # -1 ("rest of the devices") is a data-axis-only idiom on
                # pipeline meshes; a negative size here would slip through
                # the coverage check by sign cancellation
                raise ValueError(
                    f"parallel.pipeline/fsdp/tensor/sequence must be >= 1 "
                    f"on a pipeline mesh (got pipeline={pipe}, fsdp={fsdp}, "
                    f"tensor={tensor}, sequence={sequence}); only "
                    "parallel.data may be -1"
                )
            data = parallel_config.data
            if data == -1:
                data = len(devices) // (pipe * tensor * fsdp * sequence)
            if data * pipe * tensor * fsdp * sequence != len(devices):
                # loud, like _resolve_axis_sizes — silently idling devices
                # is worse than making the user restrict `devices`
                raise ValueError(
                    f"data={data} x pipeline={pipe} x fsdp={fsdp} x "
                    f"tensor={tensor} x sequence={sequence} covers "
                    f"{data * pipe * tensor * fsdp * sequence} "
                    f"devices but {len(devices)} are available; adjust "
                    "parallel.* or pass a device subset"
                )
            mesh = make_pipe_mesh(pipe, devices=devices, tensor=tensor,
                                  fsdp=fsdp, sequence=sequence)
            # Kernel dispatch reads the devices from the registered mesh;
            # a pipe mesh resolves to the XLA paths (the GPipe program is
            # already manual over (data, pipe) — ops/attention.kernel_mode).
            from trlx_tpu.ops.attention import set_active_pallas_mesh

            set_active_pallas_mesh(mesh)
            logger.info(
                f"Device mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))}"
            )
            return PipeMeshRuntime(mesh=mesh)
        if getattr(parallel_config, "pipeline_interleave", 1) not in (1, None):
            raise ValueError(
                "parallel.pipeline_interleave requires parallel.pipeline > 1 "
                "(virtual stages interleave an existing pipeline)"
            )
        mesh = make_mesh(
            data=parallel_config.data,
            fsdp=parallel_config.fsdp,
            tensor=parallel_config.tensor,
            sequence=parallel_config.sequence,
            dcn_data=getattr(parallel_config, "dcn_data", 1),
            devices=devices,
        )
        logger.info(f"Device mesh: {dict(zip(MESH_AXES, mesh.devices.shape))}")
        # Register for Pallas kernel dispatch (ops/attention.kernel_mode):
        # one TPU device calls the flash/fused-CE kernels directly, several
        # run them shard_map-wrapped over this mesh.
        from trlx_tpu.ops.attention import set_active_pallas_mesh

        set_active_pallas_mesh(mesh)
        return cls(mesh=mesh)

    @property
    def dp_size(self) -> int:
        """Total data-parallel ways (data x fsdp axes)."""
        shape = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        return shape["data"] * shape["fsdp"]

    @property
    def n_devices(self) -> int:
        return self.mesh.devices.size

    def sharding(self, *spec) -> NamedSharding:
        return NamedSharding(self.mesh, P(*spec))

    @property
    def batch_sharding(self) -> NamedSharding:
        """Shard the batch dim over all data-parallel axes."""
        return self.sharding(("data", "fsdp"))

    @property
    def replicated(self) -> NamedSharding:
        return self.sharding()

    @property
    def stacked_batch_sharding(self) -> NamedSharding:
        """Sharding for [n_steps, batch, ...] stacks: step dim replicated
        (it feeds lax.scan), batch dim over the DP axes."""
        return self.sharding(None, ("data", "fsdp"))

    def shard_batch_stacked(self, batch):
        """Place a [n_steps, batch, ...] stacked batch pytree."""
        sharding = self.stacked_batch_sharding
        replicated = self.replicated
        dp = self.dp_size

        def _place(x):
            if hasattr(x, "shape") and getattr(x, "ndim", 0) >= 2:
                arr = np.asarray(x)
                target = sharding if arr.shape[1] % dp == 0 else replicated
                return jax.device_put(arr, target)
            return x

        return jax.tree_util.tree_map(_place, batch)

    def shard_batch(self, batch):
        """Place a host batch pytree onto the mesh, batch-dim sharded over
        the DP axes. Leaves whose leading dim doesn't divide the DP ways
        (e.g. a ragged final eval batch) are replicated instead. Non-array
        leaves pass through untouched."""
        sharding = self.batch_sharding
        replicated = self.replicated
        dp = self.dp_size

        def _place(x):
            if hasattr(x, "shape") and getattr(x, "ndim", 0) >= 1:
                arr = np.asarray(x)
                target = sharding if arr.shape[0] % dp == 0 else replicated
                return jax.device_put(arr, target)
            return x

        return jax.tree_util.tree_map(_place, batch)


@dataclass
class PipeMeshRuntime(MeshRuntime):
    """Mesh runtime over ("data", "pipe") axes for GPipe trainers
    (trlx_tpu/trainer/pipelined_sft_trainer.py). Batches shard over
    "data"; block params live stacked and sharded over "pipe"."""

    @property
    def dp_size(self) -> int:
        shape = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        return shape["data"]

    @property
    def n_stages(self) -> int:
        shape = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        return shape["pipe"]

    @property
    def batch_sharding(self) -> NamedSharding:
        return self.sharding("data")

    @property
    def pipe_sharding(self) -> NamedSharding:
        return self.sharding("pipe")

    @property
    def stacked_batch_sharding(self) -> NamedSharding:
        return self.sharding(None, "data")

    @property
    def decode_mesh(self) -> Mesh:
        """("data", "fsdp", "tensor") view of the SAME devices with the
        pipe axis folded into fsdp. Generation/export under pipeline
        parallelism reshards the unstacked param view over THIS mesh
        (pipelined_mixin.standard_params): every matrix leaf splits over
        fsdp' = pipe x fsdp (plus tensor), so the decode program holds
        1/(pipe*fsdp*tensor) of the model per chip instead of a full
        replicated copy — params fit whenever the devices that run the
        pipeline fit them, which is the regime PP exists for. The
        reference instead decodes through the pipeline every token
        (modeling_nemo_ppo.py:1028-1093, generate :1158-1222); folding
        pipe into a ZeRO-style weight axis keeps the decoder a single
        program and lets XLA prefetch each layer's all-gather behind the
        previous layer's compute."""
        cached = getattr(self, "_decode_mesh", None)
        if cached is None:
            d, p, f, t, s = self.mesh.devices.shape
            # Merge ADJACENT axes only — (d, p*f, t*s) — so the flat device
            # order matches the training mesh exactly: standard_params jits
            # with inputs committed on the training mesh and out_shardings
            # on this one, and a permuted device assignment would make that
            # program unloadable (DeviceAssignmentMismatch). Sequence
            # devices therefore fold into the decode TENSOR axis (cached
            # decode is a single-sequence-shard program; ring only runs in
            # training) — Megatron-style decode sharding over t*s ways.
            arr = self.mesh.devices.reshape(d, p * f, t * s)
            cached = Mesh(arr, ("data", "fsdp", "tensor"))
            self._decode_mesh = cached
        return cached
