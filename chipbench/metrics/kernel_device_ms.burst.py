"""Device time per wave: every device op, of whatever program, that ran
inside a wave's ``orchestrate_batch`` span (profiler trace)."""


def read(run):
    tr = run.trace
    if tr is None or not tr.has_device:
        return None
    waves = tr.spans("orchestrate_batch")
    if not waves:
        return None
    return sum(tr.device_ns_in(a, b) for a, b in waves) / len(waves) / 1e6
