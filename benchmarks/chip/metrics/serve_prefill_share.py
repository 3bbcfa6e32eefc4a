"""Share of the device's busy time spent in prefill programs, in percent
(trace).  Prefill is found by the name jit gives it today, ``jit_pf``
(ServingEngine._pilot_runtime)."""
PROGRAM = "jit_pf"


def read(view):
    t = view.trace
    if t is None or t.busy_s <= 0:
        return None
    return 100.0 * sum(t.program_seconds(PROGRAM)) / (t.busy_s * t.chips)
