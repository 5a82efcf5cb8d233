"""Prefill attention: the attention of a tick that carries prompt chunks,
with the decode rows that ride in it. The engine runs it as the packed or
the padded chunk-attention Pallas kernel.

Work is counted at live lengths: a prompt chunk of n tokens at offset o
needs 4 * heads * head_dim * (n * o + n (n + 1) / 2) operations and reads
its K and V up to o + n once, whatever chunk, bucket or block the
implementation chose.
"""
from bench import ticks

TICK_KINDS = (1, 2)               # "packed" and "padded" ticks
MODULES = r"prefill_packed|prefill_chunk"
OPS = r"^(packed_)?chunk_attention"


def work(tick, dims, dtype_bytes=2):
    rows = list(tick.prefill) + [(o, 1) for o in tick.decode]
    return ticks.attention_work(rows, dims, dtype_bytes)
