"""Host time of the search front end per answered query: the self time of
the program's ``search.plan`` and ``search.cache`` spans."""


def read(ctx):
    names = ("search.plan", "search.cache")
    if not ctx.completed or not any(s.name in names for s in ctx.spans):
        return None
    return 1e3 * ctx.span_self_s(*names) / len(ctx.completed)
