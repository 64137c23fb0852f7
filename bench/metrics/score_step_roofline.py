"""score_step_roofline.<cell>: the score step's XLA program (the
``jit_score`` module) against its roofline.  The least time the chip could
take for one step (``counts.least_seconds`` of ``run.step``, the counts of
the configuration's architecture module: the larger of operations over
peak FLOP/s and bytes over peak bandwidth, both lower bounds), times the
runs in the traced window, over the module's summed device time there."""

from bench import counts


def read(run):
    tr = run.trace
    if not tr or not tr["module_count"] or tr["module_s"] <= 0:
        return None
    least = counts.least_seconds(run.step, run.peak)
    return 100.0 * least * tr["module_count"] / tr["module_s"]
