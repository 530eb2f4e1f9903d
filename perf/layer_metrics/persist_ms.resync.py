"""Mean link-persist time per engine batch, ms
(``duke_engine_phase_seconds{phase="persist"}``, host clock)."""


def read(ctx):
    s = ctx.hist_mean("duke_engine_phase_seconds", phase="persist")
    return None if s is None else s * 1000.0
