"""95th percentile latency of every answered request, timed from when it
was due."""

from bench import stats


def read(ctx):
    lat = ctx.latencies_ms()
    return stats.percentile(lat, 95) if lat else None
