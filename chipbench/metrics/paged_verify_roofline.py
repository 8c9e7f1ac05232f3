"""Kernels: the paged verify kernel's share of its roofline (%) over the
window's chunked prefill: least time of every prompt chunk
(``costs.paged_verify``) over its summed time in the device trace."""


def read(run):
    t = run.trace.kernel_s.get("paged_verify") if run.trace else None
    if not t or run.work.verify_kernel <= 0:
        return None
    return 100.0 * run.work.verify_kernel / t
