"""Host time of the store's front end per answered query: the self time of
the program's ``store.compile`` and ``store.sum`` spans (predicate compile,
group choice, program lookup and the weighting of the cards)."""


def read(ctx):
    names = ("store.compile", "store.sum")
    if not ctx.completed or not any(s.name == "store.sum"
                                    for s in ctx.spans):
        return None
    return 1e3 * ctx.span_self_s(*names) / len(ctx.completed)
