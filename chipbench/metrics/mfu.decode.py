"""Model step: model FLOPs of the window's decode tokens over the device
time of the decode programs times the chip's bf16 peak (%).  It bounds
the paged decode kernel's share: it stays when a kernel leaves the path."""


def read(run):
    t = run.trace.module_s.get("decode") if run.trace else None
    if not t or run.work.decode_flops <= 0:
        return None
    return 100.0 * run.work.decode_flops / (t * run.peak["bf16_flops"])
