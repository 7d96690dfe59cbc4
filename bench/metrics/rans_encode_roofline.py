"""The rANS encode engine's share of its roofline over the traced window."""
from bench.roofline import share


def read(run):
    return share("rans_encode", run)
