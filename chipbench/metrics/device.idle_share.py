"""Device: share of the traced window (the window and the drain of its
last answers) in which no operation ran on the chip (%)."""


def read(run):
    if (run.trace is None or not run.trace.devices
            or run.trace.window_s <= 0):
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
