"""after_idle_mean_ms: the mean time from when it was due to its entry
stage's result (its cold start, not the chain behind it), over the
requests whose entry endpoint had no scheduled arrival for longer than the
keep-alive.  A mean, not a median: chain entries, whose cold start shares
the host with the trigger's freshen of the next stage, and single entries
form two groups, and a median falls between them and swings."""


def read(run):
    lat = run.first_ms[run.ok & run.requests["idle"]]
    return float(lat.mean()) if len(lat) else None
