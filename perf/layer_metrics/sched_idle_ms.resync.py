"""Time the ingest dispatcher waits for work, per microbatch, ms: the
``sched.starved`` (every queue empty) and ``sched.coalesce`` (holding an
under-filled batch) program spans in the traced window, over its
``sched.microbatch`` spans (``engine/scheduler.py``; ``perf/spans.py``)."""

import spans


def read(ctx):
    run = spans.of_run(ctx)
    stats = run["span_stats"] if run else {}
    batches = stats.get("sched.microbatch", {}).get("count", 0)
    if not batches:
        return None
    waits = sum(stats.get(name, {}).get("seconds", 0.0)
                for name in ("sched.starved", "sched.coalesce"))
    return 1000.0 * waits / batches
