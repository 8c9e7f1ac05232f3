"""Context engine: share of the window's weight-load time that overlapped
execution (%), from the deltas of ``ctx.hidden_load_seconds`` and
``ctx.load_seconds``.  Nothing to read in a window without loads."""


def read(run):
    dt = run.ctx.get("load_seconds", 0.0)
    if dt <= 0:
        return None
    return 100.0 * run.ctx["hidden_load_seconds"] / dt
