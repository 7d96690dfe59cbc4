"""MAX of one series over [t0, t1), exact (``eps=0``)."""


def call(engine, series, t0, t1, arg):
    return engine.aggregate(series, "max", t0, t1, eps=0.0)


def ref(raw, arg):
    return float(raw.max())
