"""The cone scan kernel's share of its roofline over the traced window."""
from bench.roofline import share


def read(run):
    return share("cone_scan", run)
