"""Golden fixture: span-parity MUST flag every violation in here.

Run with options ``{"src_paths": ("",), "test_paths": (),
"schema": ("exec", "plan"), "host_schema": ("plan.wave",)}`` — six
findings: two kinds missing from the schema, two computed (non-literal)
kinds, a wall-clock name missing from the host schema and a computed one.
"""


def emit(tracer, tid, now):
    tracer.event(tid, "rogue_kind", now)                      # not in schema
    tracer.add_span(tid, "other_rogue", now, now + 1.0)       # not in schema
    kind = "exec"
    tracer.open_span(tid, kind, now)                          # computed kind
    tracer.event(tid, "pl" + "an", now)                       # computed kind


def time_it(hostspans, name):
    with hostspans.span("plan.rogue"):                        # not in schema
        pass
    hostspans.tally(name, 1, 10)                              # computed name
