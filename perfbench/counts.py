"""Operations and bytes at the published widths, counted from the traffic's
own lengths, never from the shapes a kernel pads to.  The roofline and
``mfu`` readers divide these by device time, so they hold whatever later
implements a kernel.  Multiply-adds count as two operations.
"""

from __future__ import annotations

import json
import math
from pathlib import Path

from .model import BLOCK_LEAVES, TOP_LEAVES, Dims

HERE = Path(__file__).resolve().parent
BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def params(dims: Dims) -> int:
    """Every parameter the chip holds: blocks, embedding, head, norms."""
    per_block = sum(math.prod(dims.leaf_shape(n)) for n, _ in BLOCK_LEAVES)
    top = sum(math.prod(dims.leaf_shape(n)) for n, _ in TOP_LEAVES)
    return dims.layers * per_block + top


def matmul_params_per_block(dims: Dims) -> int:
    return sum(math.prod(dims.leaf_shape(n)) for n, ax in BLOCK_LEAVES
               if ax is not None)


def kv_bytes_per_token(dims: Dims) -> int:
    return dims.layers * 2 * dims.kv_heads * dims.head_dim * \
        BYTES[dims.dtype]


def _causal_keys(start: int, end: int) -> int:
    """Keys attended by the queries at positions [start, end): each sees
    itself and every earlier position."""
    return end * (end + 1) // 2 - start * (start + 1) // 2


def attention_flops(dims: Dims, start: int, end: int) -> int:
    """QK^T and PV for queries [start, end) over their causal context, all
    layers."""
    return 4 * dims.heads * dims.head_dim * _causal_keys(start, end) * \
        dims.layers


def prefill_attention_bytes(dims: Dims, start: int, end: int) -> int:
    """Least bytes to prefill [start, end): the context's K and V read once,
    the queries read and the outputs written, all layers."""
    b = BYTES[dims.dtype]
    kv = 2 * end * dims.kv_heads * dims.head_dim * b
    qo = 2 * (end - start) * dims.heads * dims.head_dim * b
    return (kv + qo) * dims.layers


def decode_attention_bytes(dims: Dims, ctx: int) -> int:
    """One decode query over ``ctx`` keys (the new token included)."""
    return prefill_attention_bytes(dims, ctx - 1, ctx)


def forward_flops(dims: Dims, start: int, end: int, emits: int) -> int:
    """A forward pass over positions [start, end) of one sequence that
    produces ``emits`` tokens: every block's matmuls, attention, and the
    output head on the emitting positions."""
    mm = 2 * matmul_params_per_block(dims) * dims.layers * (end - start)
    head = 2 * dims.d_model * dims.vocab * emits
    return mm + attention_flops(dims, start, end) + head


def peaks(device_kind: str, path: Path = HERE / "peaks.json") -> dict:
    table = json.loads(path.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table)}")
    return table[device_kind]
