"""Device programs launched in the traced window per Lloyd iteration:
map_reduce's fold and the task engine's dispatch, counted on the device
(trace: one ``XLA Modules`` event per program run)."""


def read(view):
    if view.trace is None or not view.records["iterations"]:
        return None
    return view.trace.program_runs() / view.records["iterations"]
