"""Device idle share of the window: one minus the union of device-op
intervals over the window's length (profiler trace)."""


def read(run):
    return None if run.trace is None else run.trace.idle_pct()
