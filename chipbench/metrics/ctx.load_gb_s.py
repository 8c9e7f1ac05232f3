"""Context engine: host-to-device weight bandwidth of the window's loads
(GB/s), from the deltas of ``ctx.bytes_loaded`` and ``ctx.load_seconds``
(each load timed to ``block_until_ready``)."""


def read(run):
    dt = run.ctx.get("load_seconds", 0.0)
    if dt <= 0:
        return None
    return run.ctx["bytes_loaded"] / dt / 1e9
