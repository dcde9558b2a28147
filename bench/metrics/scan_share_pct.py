"""Share of the stack's container slots the top-k scoring reads: the sum of
``slots_scanned`` over the window's ``search.score`` spans over the sum of
their ``slots_total``, in percent. A program whose scoring spans carry no
``slots_scanned`` reports nothing."""


def read(ctx):
    spans = [s for s in ctx.spans
             if s.name == "search.score" and "slots_scanned" in s.attrs]
    total = sum(s.attrs["slots_total"] for s in spans)
    if not total:
        return None
    return 100.0 * sum(s.attrs["slots_scanned"] for s in spans) / total
