"""The single-token state update's share of its roofline, which is memory
bandwidth: the bytes the traced decode steps' live states had to move
(each float32 state read once and written once in each state-space layer,
``arith_hybrid.ssm_update_bytes``) over the HBM peak, divided by the summed
device time of the ``ssm_state_update`` operations inside ``jit_decode``
runs (the Mosaic kernel's name, or the named scope of the XLA form)."""

import arith_hybrid
import readers


def read(ctx):
    tr, peaks, d = ctx.get("trace"), ctx.get("peaks"), ctx.get("hybrid")
    steps = readers.traced_steps(ctx)
    if not tr or not peaks or not d or not steps:
        return None
    dev_s = sum(sec for module, label, sec, _ in tr["ops"]
                if module.startswith("jit_decode")
                and "ssm_state_update" in label)
    if dev_s <= 0:
        return None
    need = arith_hybrid.ssm_update_bytes(d, sum(s[1] for s in steps))
    return 100.0 * (need / peaks[1]) / dev_s
