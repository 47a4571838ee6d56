# Compute ops: attention kernels (pallas flash attention on TPU, XLA
# fallback elsewhere) and fused building blocks. flake8: noqa
import typing as tp

from .attention import (
    dot_product_attention, flash_attention, sharded_flash_attention,
)
# NOTE: the paged_attention FUNCTION is deliberately not re-exported
# here — it would shadow the `flashy_tpu.ops.paged_attention` submodule
# attribute; reach it via the module, like the serve engine does. The
# paged_decode exports below are safe: none of them share the
# submodule's name (a regression test imports both spellings).
from .paged_attention import (
    block_bytes, gather_kv, init_pool, paged_write, pool_bytes, slot_kv,
)
from .paged_decode import (
    decode_read_bytes_per_token, fused_paged_attention,
    fused_speculative_verify,
)
from .losses import chunked_softmax_cross_entropy, lm_next_token_loss

# The tuning exports resolve lazily (PEP 562, the parallel/__init__
# zero convention): `python -m flashy_tpu.ops.tuning --show/--clear`
# must not double-execute the module (runpy RuntimeWarning + a second
# in-memory cache) just because the package eagerly imported it.
_TUNING_EXPORTS = (
    "lookup_tuned_blocks", "lookup_tuned_paged_blocks",
    "lookup_tuned_bwd_blocks", "lookup_remat_policy",
    "tune_flash_blocks", "tune_paged_blocks",
    "tune_flash_bwd_blocks", "search_remat_policy",
)


def __getattr__(name: str) -> tp.Any:
    if name == "tuning":
        # the submodule attribute the eager import used to bind as a
        # side effect (`ops.tuning.tune_paged_blocks(...)` is API)
        import importlib
        return importlib.import_module(f"{__name__}.tuning")
    if name in _TUNING_EXPORTS:
        from . import tuning
        return getattr(tuning, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__() -> tp.List[str]:
    return sorted(list(globals()) + ["tuning"] + list(_TUNING_EXPORTS))
