"""How many samples of one series over [t0, t1) exceed ``arg``, exact
(``eps=0``)."""


def call(engine, series, t0, t1, arg):
    return engine.count_where(series, "gt", arg, t0, t1, eps=0.0)


def ref(raw, arg):
    return float((raw > arg).sum())
