"""Requests answered correctly inside the window, per second of it."""


def read(ctx):
    done = sum(1 for r in ctx.records
               if r.correct and ctx.t0 <= r.t_done < ctx.t1)
    return done / (ctx.t1 - ctx.t0)
