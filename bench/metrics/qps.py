"""Queries answered inside the window per second of the window."""

from bench import stats


def read(ctx):
    done = sum(1 for r in ctx.completed if r.done <= ctx.seconds)
    return stats.rate(done, ctx.seconds) if done else None
