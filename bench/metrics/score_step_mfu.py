"""score_step_mfu.<cell>: the whole serving path's share of the chip's
peak: the operations needed by the request rows that finished inside the
traced window (one row of the score step per stage served, ``run.row_step``
from the configuration's architecture module), over the window's length
times peak bf16 FLOP/s.
Padding, idle time and host overhead all lower it; it bounds every
kernel's share from above."""


def read(run):
    tr = run.trace
    if not tr or tr["window_s"] <= 0:
        return None
    lo, hi = run.trace_mark
    r = run.requests
    inside = run.ok & (r["done"] >= lo) & (r["done"] <= hi)
    rows = float((r["tokens"][inside] / run.mix["prompt_len"]).sum())
    if not rows:
        return None
    flops = rows * run.row_step["flops"]
    return 100.0 * flops / (tr["window_s"] * run.peak["bf16_flops_per_s"])
