"""Host time per microbatch converting entities to records, writing the
record store and stamping the index synced, ms: the ``ingest.convert``,
``ingest.store`` and ``ingest.stamp`` program spans in the traced window,
over its ``sched.microbatch`` spans (``engine/workload.py``
``_run_merged``; ``perf/spans.py``)."""

import spans


def read(ctx):
    run = spans.of_run(ctx)
    stats = run["span_stats"] if run else {}
    batches = stats.get("sched.microbatch", {}).get("count", 0)
    if not batches:
        return None
    host = sum(stats.get(name, {}).get("seconds", 0.0)
               for name in ("ingest.convert", "ingest.store", "ingest.stamp"))
    return 1000.0 * host / batches
