"""Context engine: weight loads into a device slot during the window
(the delta of ``ctx.loads``); each one is a whole model crossing host to
device."""


def read(run):
    return run.ctx.get("loads")
