"""Set-up: seconds from process start to the first timed operation,
compilation, weights and warm-up included (host clock)."""


def read(view):
    return view.records["setup_s"]
