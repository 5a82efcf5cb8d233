"""Share of the prefill attention kernels' roofline (%), packed and padded
chunk ticks of the traced window, decode rows riding in them included;
work at live lengths (bench/kernels/prefill_attention.py)."""
from bench import roofline
from bench.harness import load_module


def read(run):
    k = load_module("bench/kernels/prefill_attention.py")
    return roofline.share(run, k, "prefill_attention_roofline")
