"""Kernels: the paged decode kernel's share of its roofline (%): the least
time its calls needed (bytes or operations of every served decode token
over the chip's peaks, ``costs.paged_decode``) over its summed time in the
device trace."""


def read(run):
    t = run.trace.kernel_s.get("paged_decode") if run.trace else None
    if not t or run.work.decode_kernel <= 0:
        return None
    return 100.0 * run.work.decode_kernel / t
