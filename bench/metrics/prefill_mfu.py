"""Model step: the prompt FLOPs the requests prefilled in the traced
interval require (2 x active parameters per token, top-k experts only,
causal attention, the first token's head) over the prefill-chunk programs'
device time at the chip's peak."""
from bench import layers, work


def read(run):
    if layers.inside(run) is None:
        return None
    dev = run.trace.module_seconds("prefill_chunk")
    if dev <= 0:
        return None
    fl = sum(work.prefill_flops(run.shape, r.prompt_len)
             for r in layers.prefilled(run))
    return 100.0 * fl / (dev * run.peak["bf16_flops_per_s"])
