"""Mean time the HTTP handler spends turning a POST body into entities
and validating its path, ms: the ``http.parse`` program spans in the
traced window (``service/app.py`` ``_handle_post_batch``;
``perf/spans.py``)."""

import spans


def read(ctx):
    run = spans.of_run(ctx)
    row = run and run["span_stats"].get("http.parse")
    if not row or not row["count"]:
        return None
    return 1000.0 * row["seconds"] / row["count"]
