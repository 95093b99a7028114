"""Kernels: least time of the prefill attention the prompts prefilled in
the traced interval require (causal pairs; queries, live K/V and output
moved once per chunk) at the chip's peaks, over the Pallas flash kernel's
device time."""
from bench import layers, work


def read(run):
    if layers.inside(run) is None:
        return None
    k = run.trace.kernel_seconds("flash_prefill")
    if k <= 0:
        return None
    fl = by = 0.0
    for r in layers.prefilled(run):
        f, b = work.flash_prefill_work(run.shape, r.prompt_len,
                                       run.engine["chunk"])
        fl, by = fl + f, by + b
    return 100.0 * work.roofline_seconds(fl, by, run.peak)[0] / k
