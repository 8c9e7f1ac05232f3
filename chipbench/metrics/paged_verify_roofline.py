"""Kernels: the paged verify kernel's share of its roofline (%) over the
window's chunked prefill: least time of every prompt chunk (the family's
``request_work``) over its summed time in the device trace.  Nothing to
read where the family counts no such kernel or the trace holds none."""


def read(run):
    t = run.trace.kernel_s.get("paged_verify") if run.trace else None
    least = run.work.kernel.get("paged_verify", 0.0)
    if not t or least <= 0:
        return None
    return 100.0 * least / t
