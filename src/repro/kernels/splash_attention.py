"""JAX's splash attention kernel for the model's training/prefill path: tiles
chosen from the shapes, and the kernel built once per shape.

The kernel (``jax.experimental.pallas.ops.tpu.splash_attention``) is causal
flash attention with block-sparse masks: a block that the mask leaves empty
is skipped in compute and in DMA, grouped query heads share their K/V head
without a copy, and dQ and dK/dV have kernels of their own.  The jitted,
differentiable wrapper in the model's layout is ``kernels.ops.splash_attention``.
"""

from __future__ import annotations

import functools
import json

import jax
from jax._src import tpu_custom_call
from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_kernel as splash
from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_mask as splash_mask

#: The largest splash tile along either sequence axis, and the widest slice of
#: a K/V tile one matmul takes: the fastest of the tilings swept for granite's
#: causal S=4096, D=128 on a TPU v5e (PERF.md), with the fused backward.
SPLASH_BLOCK = 1024
SPLASH_COMPUTE = 512


def splash_blocks(S: int, T: int):
    """The splash tiles for an S x T attention: square tiles of the largest of
    SPLASH_BLOCK, SPLASH_BLOCK/2, ..., 128 that divides both lengths, the same
    in the forward and the fused backward kernel; None where none does."""
    b = SPLASH_BLOCK
    while b >= 128 and (S % b or T % b):
        b //= 2
    if b < 128:
        return None
    c = min(b, SPLASH_COMPUTE)
    return splash.BlockSizes(
        block_q=b, block_kv=b, block_kv_compute=c,
        block_q_dkv=b, block_kv_dkv=b, block_kv_dkv_compute=c,
        use_fused_bwd_kernel=True,
    )


def splash_fits(S: int, T: int, Hq: int, Hkv: int, D: int) -> bool:
    """Whether ``kernels.ops.splash_attention`` takes these shapes."""
    return splash_blocks(S, T) is not None and D % 128 == 0 and Hq % Hkv == 0


def _one_line_json(**kwargs) -> bytes:
    return json.dumps(kwargs, sort_keys=True, separators=(",", ":")).encode("ascii")


@functools.lru_cache(maxsize=64)
def splash_kernel(S: int, T: int, Hq: int, window: int | None, blocks, interpret: bool = False):
    """The splash kernel for one shape and tiling, on (Hq, S, D) queries and
    (Hkv, T, D) keys and values.  Its block-sparse mask metadata is built here,
    on the host, once per shape."""
    # JAX writes a kernel's metadata as JSON with ``indent=0``, which breaks
    # its custom-call over several lines of the compiled HLO text; readers
    # that map device ops to scope paths line by line (core/hlo_tree.py,
    # chipbench/trace.py) then miss the kernel.  The same JSON on one line.
    tpu_custom_call._compact_json_object = _one_line_json
    if window is None:
        mask = splash_mask.CausalMask((S, T))
    else:  # k <= q and q - k < window, as models/attention.py:_mask
        mask = splash_mask.LocalMask((S, T), window_size=(window - 1, 0), offset=0)
    with jax.ensure_compile_time_eval():  # concrete metadata, kept across traces
        return splash.make_splash_mha(
            splash_mask.MultiHeadMask([mask] * Hq), block_sizes=blocks, head_shards=1, q_seq_shards=1,
            interpret=interpret,
        )
