"""Model FLOP/s utilisation: 3 x forward operations per image
(``arith.count_flops``; backward taken as twice the forward, recomputation
not counted) x images/s/chip of this run's window (the ``--trace 0``
definition of ``train_img_s``) over the chip's bf16 peak."""


def read(ctx):
    peaks = ctx.get("peaks")
    if not peaks or not ctx.get("images_per_s_per_chip"):
        return None
    return 100.0 * 3 * ctx["fwd_flops_per_image"] * \
        ctx["images_per_s_per_chip"] / peaks[0]
