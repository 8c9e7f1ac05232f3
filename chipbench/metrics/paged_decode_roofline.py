"""Kernels: the paged decode kernel's share of its roofline (%): the least
time its calls needed (bytes or operations of every served decode token
over the chip's peaks, the family's ``request_work``) over its summed time
in the device trace.  Nothing to read where the family counts no such
kernel or the trace holds none."""


def read(run):
    t = run.trace.kernel_s.get("paged_decode") if run.trace else None
    least = run.work.kernel.get("paged_decode", 0.0)
    if not t or least <= 0:
        return None
    return 100.0 * least / t
