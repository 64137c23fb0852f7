"""expert_imbalance.<cell>: how unevenly the router loads the experts this
chip holds.  Per expert layer, the largest held expert's rows over the mean
rows per held expert, from the score step's counter of routed rows
(``ModelEndpoint.code`` records it on each invocation span as
``expert_rows``, one list of rows per held expert for each expert layer),
averaged over the layers; the mean over the window's invocations.  1 is an
even load: the step's grouped expert products wait on the fullest expert.
A program without the counter gives no reading."""


def read(run):
    per_call = []
    for span in run.spans:
        layers = [r for r in span.attrs.get("expert_rows", ()) if sum(r)]
        if layers:
            per_call.append(sum(max(r) * len(r) / sum(r) for r in layers)
                            / len(layers))
    return sum(per_call) / len(per_call) if per_call else None
