"""Memory-bandwidth utilisation of the decode program: all the bytes the
traced decode steps needed (every weight that is not a routed expert's
once a step, the held experts that got a row, the live requests' states
read and written, the attention layer's K and V over the real contexts;
``arith_branch.decode_step_bytes``) over the HBM peak, divided by the
summed device time of the ``jit_decode`` runs in the trace."""

import arith_branch
import readers
import span_readers
import trace_reduce


def read(ctx):
    tr, peaks, d = ctx.get("trace"), ctx.get("peaks"), ctx.get("branch")
    span = ctx.get("trace_span")
    steps = [s for s in readers.traced_steps(ctx) if s[1]]
    spans = span_readers.in_window(ctx, "serve.decode")
    if not tr or not peaks or not d or not steps or not spans:
        return None
    hit = [s[span_readers.ARGS]["moe_experts_hit"]
           for s in span_readers.named(spans, "serve.decode")
           if "moe_experts_hit" in s[span_readers.ARGS]
           and span[0] <= s[span_readers.START] <= span[1]]
    dev_s = trace_reduce.module_time(tr, "jit_decode")
    if dev_s <= 0 or not hit:
        return None
    # the traced decode passes' experts, spread over the traced steps
    per_step = sum(hit) / len(hit)
    need = sum(arith_branch.decode_step_bytes(d, s[1], s[2], per_step)
               for s in steps)
    return 100.0 * (need / peaks[1]) / dev_s
