"""Decode attention: the attention of a pure-decode tick, where every row
feeds one token and attends its whole live context.

The engine runs it as the chunk-attention Pallas kernel inside the
pure-decode program. Its work is counted at live lengths: a row at
position p attends p + 1 keys, whatever kv bucket or block the
implementation reads.
"""
from bench import ticks

TICK_KINDS = (0,)                 # the engine profiler's "decode" ticks
MODULES = r"mixed_decode"         # program that runs them
OPS = r"^chunk_attention"     # the Pallas kernel's custom call


def work(tick, dims, dtype_bytes=2):
    """(operations, bytes) of one layer for one tick."""
    return ticks.attention_work([(o, 1) for o in tick.decode], dims,
                                dtype_bytes)
