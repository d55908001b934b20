"""Bytes one segmented tiered-gather call needs, counted from its shapes.

Each gather reads its page's row from the tier that holds it and writes it
out dequantized to float32. A near row is ``D`` float32 values; a far row
is ``D`` int8 values and one float32 scale. Every gather also reads its
page id, tier bit, slot, segment index and scale (five 4-byte values).
Padding gathers are not counted: they are not work the step needed.

    bytes = near * 4 * D + far * (D + 4) + (near + far) * (4 * D + 20)

Worked example, smollm-360m's store (D = 2 * 32 layers * 5 heads * 64 =
20,480): a call with 2,000 near and 1,000 far ids needs 2,000 * 81,920 +
1,000 * 20,484 + 3,000 * 81,940 = 430,144,000 bytes; at 819 GB/s that is
0.525 ms, the least time the call can take. The dequantizing multiply, D
operations per far row, is 20,480,000 operations, which bind nothing, so
the bandwidth bound applies.
"""
from __future__ import annotations


def row_dim(model: dict) -> int:
    """The store's row width: K and V of every layer and KV head."""
    hd = model["hidden_size"] // model["num_attention_heads"]
    return 2 * model["num_hidden_layers"] * model["num_key_value_heads"] * hd


def bytes_needed(near: int, far: int, d: int) -> int:
    return near * 4 * d + far * (d + 4) + (near + far) * (4 * d + 20)
