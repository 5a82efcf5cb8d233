"""Host time of one prefix-cache store (ms): the mean duration of the
``aios.prefix.store`` spans in the traced window (a slot row read to the
host, paged, and offered to the prefix cache)."""
from bench import spans


def read(run):
    if run.trace is None:
        return None
    stores = spans.named(run.trace, "aios.prefix.store")
    if not stores:
        return None
    return sum(e - s for s, e in stores) / len(stores) * 1e-6
