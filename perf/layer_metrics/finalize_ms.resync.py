"""Mean certified-finalize time per engine batch, ms: the double-double
rescore, host finalization and match events after the scan
(``duke_engine_phase_seconds{phase="score"}``, host clock)."""


def read(ctx):
    s = ctx.hist_mean("duke_engine_phase_seconds", phase="score")
    return None if s is None else s * 1000.0
