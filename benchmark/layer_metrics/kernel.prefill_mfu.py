"""Operations the traced prefill passes needed (real prompt tokens, each
attending what was cached by the end of its pass; ``arith.model_prefill_flops``)
over the prefill and chunk programs' device time times the chip's bf16 peak.
Compute-bound.  Bucket padding lowers it: padded rows are work the
algorithm did not need."""

import arith
import readers
import trace_reduce


def read(ctx):
    tr, peaks = ctx.get("trace"), ctx.get("peaks")
    passes = [p for s in readers.traced_steps(ctx) for p in s[3]]
    if not tr or not peaks or not passes:
        return None
    dev_s = trace_reduce.module_time(tr, "jit_prefill", "jit_chunk")
    if dev_s <= 0:
        return None
    return 100.0 * arith.model_prefill_flops(ctx["model"], passes) / (
        dev_s * peaks[0])
