"""``fact_per``: a count the entry handed over in ``facts``, per another."""


def reduce(ctx, fact, per):
    n, d = ctx["facts"].get(fact), ctx["facts"].get(per)
    return float(n) / d if n and d else None
