# Parallelism layer: device meshes, data/FSDP/tensor/sequence sharding,
# and collectives-based building blocks (ring attention). This is the
# performance path of the framework: where the reference reached for
# DistributedDataParallel + NCCL (flashy/distrib.py:65-75), flashy_tpu
# shards arrays over a jax.sharding.Mesh and lets XLA insert and overlap
# the collectives over ICI/DCN. flake8: noqa
from .mesh import make_mesh, default_mesh, set_default_mesh, mesh_shape_from_devices
from .data_parallel import (wrap, shard_batch, replicate, fsdp_sharding,
                            shard_params, with_grad_accumulation)
from .ring import ring_attention, ring_self_attention
from .ring_fused import fused_ring_attention
from .moe_ep import ep_dropless_moe
from .accounting import (collective_stats, compare_collective_stats,
                         memory_stats, total_collective_bytes)
# NOTE: `pipeline` (the function) intentionally shadows the submodule
# attribute, as it has since the seed — `from flashy_tpu.parallel
# import pipeline` must stay the GPipe entry point, and a lazy
# resolution would be unstable (whichever of the function export or
# the submodule import ran first would win the attribute). The
# runpy double-import warning this costs `python -m
# flashy_tpu.parallel.pipeline` is benign (the module holds no mutable
# state; the schedule cache lives in .schedules, imported once) and is
# silenced at the invocation sites with
# `-W ignore::RuntimeWarning:runpy` (Makefile pipeline-demo).
from .pipeline import pipeline, pipeline_1f1b

# ZeRO exports resolve lazily (PEP 562): `python -m
# flashy_tpu.parallel.zero` is a CLI entry point, and an eager
# `from .zero import ...` here would put the module in sys.modules
# before runpy executes it — a double-execution RuntimeWarning on
# every demo / bench run.
_LAZY_EXPORTS = {
    "zero_sharding": "zero", "zero_update": "zero",
    "per_device_bytes": "zero", "describe_state_sharding": "zero",
    # tensor parallelism: same CLI-module rule as zero
    "tensor_state_sharding": "tensor", "validate_tensor_args": "tensor",
    "flash_bwd_parity": "tensor",
    "build_1f1b_schedule": "schedules", "schedule_stats": "schedules",
    "bubble_fraction": "schedules", "gpipe_bubble_fraction": "schedules",
    # the numerics-audit program registry (analysis --numerics sweep);
    # lazy so importing the package never builds demo programs
    "numerics_audit_programs": "audit",
}


def __getattr__(name):
    module = _LAZY_EXPORTS.get(name)
    if module is not None:
        import importlib
        return getattr(importlib.import_module(f".{module}", __name__), name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(list(globals()) + list(_LAZY_EXPORTS))
