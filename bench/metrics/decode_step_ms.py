"""Device time of one pure-decode program (ms), the mean over its instances
in the traced window."""


def read(run):
    if run.trace is None:
        return None
    inst = run.trace.module_instances(r"mixed_decode")
    if not inst:
        return None
    return sum(e - s for s, e, _ in inst) / len(inst) * 1e-6
