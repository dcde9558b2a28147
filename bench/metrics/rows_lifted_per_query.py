"""Rows the program lifted to bitmap words per answered query: the sum of
``rows_lifted`` over the window's spans (``search.execute``, one per batch
program; ``search.score``, one per top-k scoring) over the answered
queries."""


def read(ctx):
    rows = [s.attrs["rows_lifted"] for s in ctx.spans
            if "rows_lifted" in s.attrs]
    if not rows or not ctx.completed:
        return None
    return sum(rows) / len(ctx.completed)
