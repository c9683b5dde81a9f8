"""CircleQuery (``materialize`` or a count) centred on a data point,
its radius drawn evenly over ``r``."""


def requests(g, f, n, rng):
    ix = rng.integers(0, len(g.x), n)
    rad = g.spread(*f["r"], n, rng)
    spec = g.core.CircleQuery(materialize=bool(f.get("materialize")))
    return [g.Request("circle", spec, (g.x[ix[i]:ix[i] + 1],
                                       g.y[ix[i]:ix[i] + 1],
                                       rad[i:i + 1]), 1)
            for i in range(n)]
