"""Engine time per wave: ``Orchestrator.step`` to the next cycle (plans
applied into T_alloc at their arrivals, execution events)."""


def read(run):
    vals = [run.spans[i].ms for i in run.steps]
    return sum(vals) / len(vals) if vals else None
