"""Requests per batched launch: the mean ``batch`` of the program's
``search.execute`` spans."""


def read(ctx):
    sizes = [s.attrs["batch"] for s in ctx.spans
             if s.name == "search.execute"]
    return sum(sizes) / len(sizes) if sizes else None
