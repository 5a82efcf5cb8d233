"""95th percentile of the gaps between consecutive streamed tokens in the
measured window (ms), the same reading as the end-to-end ``itl_p95_ms``.
A cell whose tail sits on the edge between two kinds of tick (prompt
chunks of 256 and of 512 tokens) reads it here, where it swings between
runs too far to hold it to a bound."""
from bench.harness import itl_ms, percentile


def read(run):
    return percentile(itl_ms(run.requests, run.window), 95)
