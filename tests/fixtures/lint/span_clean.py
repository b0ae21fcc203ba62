"""Golden fixture: span-parity must stay SILENT on all of this.

Run with options ``{"src_paths": ("",), "test_paths": (),
"schema": ("exec", "plan"), "host_schema": ("plan.wave",)}`` — every
emitted kind and wall-clock name is a string literal present in its
schema, and non-emission calls are ignored.
"""


def emit(tracer, tid, now):
    tracer.event(tid, "plan", now, policy="ibdash")
    sid = tracer.open_span(tid, "exec", now, device=3)
    tracer.close_span(sid, now + 1.0, outcome="ok")
    tracer.add_span(tid, "exec", now, now + 1.0, device=4)


def time_it(hostspans):
    with hostspans.span("plan.wave", apps=3) as wave:
        wave.set(planned=3)
    hostspans.tally("plan.wave", 1, 10)


def not_an_emission(queue, logger, other):
    queue.event(7)                      # one positional arg: no kind to audit
    logger.add_span()                   # no args at all
    other.span("anything")              # not the hostspans module
