"""Share of the device's idle time in the traced window during which
no program span was open on any host thread, % (``perf/spans.py``
``idle_by_span``)."""

import spans


def read(ctx):
    run = spans.of_run(ctx)
    if run is None:
        return None
    idle = sum(run["idle_by_span"].values())
    if idle <= 0:
        return 0.0
    return 100.0 * run["idle_by_span"].get(spans.NO_SPAN, 0.0) / idle
