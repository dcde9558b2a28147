"""Device time of the top-k scoring per answered query: the ops of the
jitted program ``index_score`` (module ``jit_index_score``), their union
per device plane inside the traced window. Reads ``ctx.scopes``
(``bench/scopes.py``), which the harness does not fill yet: until it does,
nothing is reported."""

from bench import scopes


def read(ctx):
    sc = getattr(ctx, "scopes", None)
    return scopes.per_query_ms(sc and scopes.program_s(sc, "index_score"),
                               len(ctx.completed))
