"""The paged-attention kernel's share of its roofline, which is memory
bandwidth: the K and V bytes the traced decode steps had to read (their
requests' real contexts, ``arith.paged_attention_bytes``) over the HBM
peak, divided by the summed device time of the ``tpu_custom_call``
operations inside ``jit_decode`` runs."""

import arith
import readers


def read(ctx):
    tr, peaks = ctx.get("trace"), ctx.get("peaks")
    steps = readers.traced_steps(ctx)
    if not tr or not peaks or not steps:
        return None
    kernel_s = sum(d for module, _, d, is_kernel in tr["ops"]
                   if is_kernel and module.startswith("jit_decode"))
    if kernel_s <= 0:
        return None
    need = arith.paged_attention_bytes(ctx["model"], [s[2] for s in steps])
    # bytes and kernel time are both summed over the devices in the trace
    return 100.0 * (need / peaks[1]) / kernel_s
