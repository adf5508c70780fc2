"""The paged-attention kernel's share of its roofline, which is memory
bandwidth, where layers see different spans of the context: the K and V
bytes the traced decode steps had to read (global layers the rows' whole
contexts, window layers at most ``window`` positions a row;
``arith_moe.paged_kv_bytes``) over the HBM peak, divided by the summed
device time of the ``paged_attention`` kernels inside ``jit_decode``
runs."""

import arith_moe
import readers


def read(ctx):
    tr, peaks, d = ctx.get("trace"), ctx.get("peaks"), ctx.get("moe")
    steps = readers.traced_steps(ctx)
    if not tr or not peaks or not d or not steps:
        return None
    dev_s = sum(sec for module, label, sec, kernel in tr["ops"]
                if kernel and module.startswith("jit_decode")
                and "paged_attention" in label)
    if dev_s <= 0:
        return None
    need = sum(arith_moe.paged_kv_bytes(d, s[1], s[2]) for s in steps)
    return 100.0 * (need / peaks[1]) / dev_s
