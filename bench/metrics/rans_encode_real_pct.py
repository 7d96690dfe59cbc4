"""rANS encode kernel: the window's real cells (plane symbols encoded) over
the cells dispatched once the rows' steps and row counts are padded to the
engine's shapes, in percent."""


def read(run):
    n = run.counters.get("rans_encode_run_cells")
    return 100.0 * run.counters["rans_encode_real_cells"] / n if n else None
