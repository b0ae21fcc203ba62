"""Orchestration self time per wave: ``orchestrate_batch`` minus the
``decide_batch`` calls inside it (wave context build and plan assembly)."""


def read(run):
    vals = [run.spans[i].ms - sum(sp.ms for sp in run.within(i, "decide_batch"))
            for i in run.waves]
    return sum(vals) / len(vals) if vals else None
