"""Cone scan kernel: the window's real cells (samples scanned) over the
cells dispatched once each length bucket's time and lanes are padded to the
kernel's shape, in percent."""


def read(run):
    n = run.counters.get("cone_scan_run_cells")
    return 100.0 * run.counters["cone_scan_real_cells"] / n if n else None
