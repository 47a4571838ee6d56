# Compute ops: attention kernels (pallas flash attention on TPU, XLA
# fallback elsewhere) and fused building blocks. flake8: noqa
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
from .paged_decode import fused_paged_attention, fused_speculative_verify
from .losses import chunked_softmax_cross_entropy, lm_next_token_loss
