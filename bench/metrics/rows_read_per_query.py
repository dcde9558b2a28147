"""Container rows the store's aggregation program read per answered query:
the sum of ``rows_read`` over the window's ``store.sum`` spans over the
answered queries. A program whose sums carry no ``rows_read`` reports
nothing."""


def read(ctx):
    rows = [s.attrs["rows_read"] for s in ctx.spans
            if s.name == "store.sum" and "rows_read" in s.attrs]
    if not rows or not ctx.completed:
        return None
    return sum(rows) / len(ctx.completed)
