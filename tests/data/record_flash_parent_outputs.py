# Recorder of tests/data/flash_parent_outputs.npz: `flash_attention`'s
# output and gradients (the fused backward) at block_q = block_k = 256,
# the tiles every training run before PR 36 compiled, in interpret mode
# on the CPU. Run on a checkout of the PARENT commit it writes the file;
# tests/test_ops.py imports `outputs` from here and runs it on the
# working tree, so both sides are one definition:
#
#   git archive <parent> | tar -x -C build/probe/parent
#   JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
#       python tests/data/record_flash_parent_outputs.py \
#       build/probe/parent tests/data/flash_parent_outputs.npz
#
# (the device count is tests/conftest.py's: XLA's CPU matrix products
# split their work by it, which float32 operands would show in the last
# bit; the cases are bfloat16, whose products are exact in float32).
#
# The file in the repo was taken from commit e9518e6 (PR 35). Record it
# anew only when a PR means to change what the kernels compute.
"""Record the flash kernels' outputs at 256 x 256 tiles from a checkout."""
import sys

import numpy as np

# name -> (q shape, k/v shape, causal, dtype): one tile on the causal
# diagonal; no mask; tiles under, on and past the diagonal; more keys
# than queries (offset 256: a tile under the diagonal and one on it).
CASES = {
    "diagonal": ((1, 256, 2, 32), (1, 256, 2, 32), True, "bfloat16"),
    "all_keys": ((1, 512, 1, 32), (1, 512, 1, 32), False, "bfloat16"),
    "self": ((1, 512, 2, 32), (1, 512, 2, 32), True, "bfloat16"),
    "longer_keys": ((1, 256, 1, 32), (1, 512, 1, 32), True, "bfloat16"),
}


def outputs() -> dict:
    """out, dq, dk, dv of every case from whatever `flashy_tpu` is
    importable, as float32 (exact for bfloat16)."""
    import jax
    import jax.numpy as jnp
    from flashy_tpu.ops import flash_attention
    found = {}
    for name, (shape_q, shape_k, causal, dtype) in CASES.items():
        rng = np.random.default_rng(36)
        q = jnp.asarray(rng.standard_normal(shape_q), dtype)
        k = jnp.asarray(rng.standard_normal(shape_k), dtype)
        v = jnp.asarray(rng.standard_normal(shape_k), dtype)

        def attend(q, k, v):
            return flash_attention(q, k, v, causal=causal, block_q=256,
                                   block_k=256)

        def loss(q, k, v):
            return (attend(q, k, v).astype(jnp.float32) ** 2).sum()

        grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        for key, value in zip(("out", "dq", "dk", "dv"),
                              (attend(q, k, v),) + grads):
            found[f"{name}/{key}"] = np.asarray(value, np.float32)
    return found


if __name__ == "__main__":
    sys.path.insert(0, sys.argv[1])
    np.savez(sys.argv[2], **outputs())
