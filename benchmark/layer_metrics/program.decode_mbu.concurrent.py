"""Memory-bandwidth utilisation of the decode program: all the bytes the
traced decode steps needed (every weight once a step, the live requests'
states read and written, the attention layers' K and V over the real
contexts; ``arith_hybrid.decode_step_bytes``) over the HBM peak, divided by
the summed device time of the ``jit_decode`` runs in the trace."""

import arith_hybrid
import readers
import trace_reduce


def read(ctx):
    tr, peaks, d = ctx.get("trace"), ctx.get("peaks"), ctx.get("hybrid")
    steps = [s for s in readers.traced_steps(ctx) if s[1]]
    if not tr or not peaks or not d or not steps:
        return None
    dev_s = trace_reduce.module_time(tr, "jit_decode")
    if dev_s <= 0:
        return None
    need = sum(arith_hybrid.decode_step_bytes(d, s[1], s[2]) for s in steps)
    return 100.0 * (need / peaks[1]) / dev_s
