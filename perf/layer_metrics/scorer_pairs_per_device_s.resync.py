"""Useful pairs per second of the scorer program's device time.

Useful pairs are counted from the benchmark's own traffic, whatever the
program does: each record a window POST carried, times the live records
it must be compared with (all others; under linkage the other group's).
The scorer's device time is the sum of the trace's XLA module events
whose name holds ``SCORER``."""

SCORER = "jit_score"


def read(ctx):
    if ctx.trace is None:
        return None
    device_s = sum(v for k, v in ctx.trace["modules"].items()
                   if SCORER in k)
    if device_s <= 0:
        return None
    datasets = ctx.service["datasets"]
    linkage = ctx.service["kind"] == "recordlinkage"
    live = {ds: {r["_id"] for r in ctx.rows[ds]} for ds in datasets}
    pairs = 0
    for p in sorted(ctx.report["posts"], key=lambda p: p["send"]):
        if p["status"] != 200:
            continue
        ds = p["dataset"]
        for e in p["entities"]:
            if e.get("_deleted"):
                live[ds].discard(str(e["_id"]))
            else:
                live[ds].add(str(e["_id"]))
        if p["phase"] != "window":
            continue
        n = sum(not e.get("_deleted") for e in p["entities"])
        if linkage:
            other = sum(len(live[d]) for d in datasets if d != ds)
        else:
            other = sum(len(v) for v in live.values()) - 1
        pairs += n * other
    return pairs / device_s
