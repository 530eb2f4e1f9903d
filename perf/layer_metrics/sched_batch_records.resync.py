"""Mean records per scheduler microbatch (``duke_sched_microbatch_records``)."""


def read(ctx):
    return ctx.hist_mean("duke_sched_microbatch_records")
