"""Mean device-scan time per engine batch, ms: the host's wait for the
scorer program's blocks (``duke_engine_phase_seconds{phase="retrieve"}``,
host clock, engine/device_matcher.py ``_score_blocks``)."""


def read(ctx):
    s = ctx.hist_mean("duke_engine_phase_seconds", phase="retrieve")
    return None if s is None else s * 1000.0
