"""Share of the traced window in which no operation ran on the device (the
mean over the devices a cell uses), in an ingest cell."""


def read(run):
    if run.trace is None or not run.trace["devices"]:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
