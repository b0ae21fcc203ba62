"""Policy host time per wave: ``decide_batch`` minus the placement-kernel
calls inside it (sorting, pad and cast, fan-out of decisions)."""


def read(run):
    vals = []
    for i in run.waves:
        decide = run.within(i, "decide_batch")
        kernels = [sp for sp in run.within(i) if sp.name.startswith("kernel:")]
        vals.append(sum(sp.ms for sp in decide) - sum(sp.ms for sp in kernels))
    return sum(vals) / len(vals) if vals else None
