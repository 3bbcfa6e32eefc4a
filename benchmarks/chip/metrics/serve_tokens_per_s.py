"""Generated tokens of the requests completed in the window, over the
window's seconds, summed over every replica (host clock)."""


def completed(records):
    t0, t1 = records["window"]
    return [q for q in records["requests"]
            if q["ok"] and q["t_done"] is not None and q["t_done"] <= t1]


def read(view):
    r = view.records
    return sum(q["out_len"] for q in completed(r)) / r["window_s"]
