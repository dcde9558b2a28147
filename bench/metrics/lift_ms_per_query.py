"""Device time of the kind lift per answered query: ops under the named
scope ``roaring.lift`` (``dispatch.lift_rows``), their union per device
plane inside the traced window. Reads ``ctx.scopes`` (``bench/scopes.py``),
which the harness does not fill yet: until it does, nothing is reported.
Where a trace carries no scope path, the metric is left out, never 0."""

from bench import scopes


def read(ctx):
    sc = getattr(ctx, "scopes", None)
    return scopes.per_query_ms(sc and scopes.scope_s(sc, "roaring.lift"),
                               len(ctx.completed))
