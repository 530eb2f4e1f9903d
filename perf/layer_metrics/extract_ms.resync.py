"""Mean feature-extraction time per engine batch, ms
(``duke_engine_phase_seconds{phase="encode"}``, host clock)."""


def read(ctx):
    s = ctx.hist_mean("duke_engine_phase_seconds", phase="encode")
    return None if s is None else s * 1000.0
