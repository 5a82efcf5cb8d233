"""Share of the decode attention kernel's roofline (%), pure-decode ticks
of the traced window; work at live lengths (bench/kernels/decode_attention.py)."""
from bench import roofline
from bench.harness import load_module


def read(run):
    k = load_module("bench/kernels/decode_attention.py")
    return roofline.share(run, k, "decode_attention_roofline")
