"""Records acknowledged per second: every record of the window's POSTs
answered 200, over the time from the window's start to the last of those
POSTs' acknowledgements (a POST started inside the window is waited for,
so a batch still in flight at the close does not quantize the rate)."""


def read(ctx):
    done = sum(len(p["entities"]) for p in ctx.posts if p["status"] == 200)
    return done / (ctx.last_ack - ctx.t0)
