"""The packed paged-attention kernel's share of its roofline, which is
memory bandwidth: the K and V bytes the traced decode steps had to read
(the attention layers only, over the rows' real contexts,
``arith_hybrid.paged_kv_bytes``) over the HBM peak, divided by the summed
device time of the ``paged_attention_packed`` operations inside
``jit_decode`` runs (the Mosaic kernel's name for heads under 128 lanes)."""

import arith_hybrid
import readers


def read(ctx):
    tr, peaks, d = ctx.get("trace"), ctx.get("peaks"), ctx.get("hybrid")
    steps = readers.traced_steps(ctx)
    if not tr or not peaks or not d or not steps:
        return None
    dev_s = sum(sec for module, label, sec, _ in tr["ops"]
                if module.startswith("jit_decode")
                and "paged_attention_packed" in label)
    if dev_s <= 0:
        return None
    need = arith_hybrid.paged_kv_bytes(d, sum(s[2] for s in steps))
    return 100.0 * (need / peaks[1]) / dev_s
