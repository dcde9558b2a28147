"""Median wait of a request in the service's queue, from submit to
admission: the duration of the program's ``search.queued`` spans."""

from bench import stats


def read(ctx):
    waits = [1e3 * (s.t1 - s.t0) for s in ctx.spans
             if s.name == "search.queued"]
    return stats.percentile(waits, 50) if waits else None
