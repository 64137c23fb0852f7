"""tokens_per_s: prompt tokens of the requests completed inside the
window, over the window's length."""


def read(run):
    r = run.requests
    done_in = run.ok & (r["done"] <= run.t0 + run.seconds)
    return float(r["tokens"][done_in].sum() / run.seconds)
