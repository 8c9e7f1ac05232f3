"""Model step: model FLOPs of the window's prompt tokens over the device
time of the prefill-chunk programs times the chip's bf16 peak (%)."""


def read(run):
    t = run.trace.module_s.get("prefill") if run.trace else None
    if not t or run.work.prefill_flops <= 0:
        return None
    return 100.0 * run.work.prefill_flops / (t * run.peak["bf16_flops"])
