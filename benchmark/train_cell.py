"""A training cell: ``mx.parallel.ShardedTrainer`` stepping one synthetic
batch that lives on the device.

The batch is made from the seed, placed ONCE (``trainer._place_batch``)
and the compiled step (``trainer._train_step``) is called back to back
with the state threaded through, as ``bench.py:_timed_steps`` does.  The
public ``trainer.step()`` converts and ships a host batch on every call
(the host's dtype conversion is what PR 22's train cell measured), so
this reaches past it for two private names; PERF.md lists "a public step
that takes a placed batch" for a later PR.  Calling past ``step()`` also
passes its ``trainer.step`` span by, so the loop wraps its own calls in
``bench.train_dispatch`` / ``bench.train_wait`` annotations.
"""

import gc
import time

import numpy as np

import arith
import reference

# Both limits are about three times the worst seen over 12 runs on the chip
# (my chip runs, PR 24: see PERF.md) and are meant to refuse arithmetic below
# bf16 (fp8 matmuls or activations, a lower-precision BatchNorm statistic)
# that a PR does not declare.
# Loss of the first step against the float32 reference, relative.  bf16
# parameters, activations and BatchNorm inputs against float32 moved the mean
# cross-entropy of 256 images by 0.02-0.06 %; a wrong layout, stride, pad or
# statistic moves it by tens of percent at initialisation, where the loss
# sits near ln(classes).
LOSS_TOL = 0.002
# Cosine between the classifier weight's first momentum buffer (-lr * grad,
# whatever the scaling) and the reference's negative gradient.  A right
# backward pass in bf16 gave 0.996; an unrelated direction gives 0.
COS_TOL = 0.99


def build(cfg, seed):
    import jax
    import mxnet_tpu as mx

    mx.random.seed(int(seed) % (2 ** 31))
    n_chips = len(jax.devices())
    hw, batch = cfg["image_size"], cfg["batch_per_chip"] * n_chips
    net = mx.models.resnet(
        num_classes=cfg["num_classes"], num_layers=cfg["num_layers"],
        image_shape=(3, hw, hw), layout=cfg["layout"], stem=cfg["stem"])
    shapes = {"data": (batch, hw // 2, hw // 2, 12),
              "softmax_label": (batch,)}
    trainer = mx.parallel.ShardedTrainer(
        net, shapes, mesh=mx.parallel.local_mesh("dp"),
        optimizer=cfg["optimizer"],
        optimizer_params=dict(cfg["optimizer_params"]),
        initializer=mx.initializer.Xavier(rnd_type="gaussian",
                                          factor_type="in", magnitude=2),
        dtype=cfg["dtype"])
    rng = np.random.default_rng(int(seed))
    batch_host = {
        "data": rng.uniform(-1, 1, shapes["data"]).astype(np.float32),
        "softmax_label": rng.integers(0, cfg["num_classes"], batch)
        .astype(np.float32)}
    return net, trainer, batch_host


def _loss(probs, labels):
    p = np.asarray(probs).astype(np.float32)
    return float(np.mean(-np.log(np.maximum(
        p[np.arange(len(labels)), labels], 1e-30))))


def run(cell):
    import jax

    cfg = cell["config"]
    tic = time.perf_counter()
    net, trainer, batch_host = cell["build"](cfg, cell["seed"])
    n_chips = len(jax.devices())
    batch = batch_host["data"].shape[0]
    labels = batch_host["softmax_label"].astype(np.int64)
    placed = trainer._place_batch(batch_host)
    one = np.float32(1.0)
    step = trainer._train_step
    before = trainer.get_params()
    cell["info"](trainer_built_s=time.perf_counter() - tic)

    # -- first step: compiles (or reads the cache), and is the one checked ---
    tic = time.perf_counter()
    params, opt_state, aux, outs, key = step(
        trainer.params, trainer.opt_state, trainer.aux, placed,
        trainer._key, one)
    loss0 = _loss(outs[0], labels)
    # SGD's first momentum buffer is -lr * grad; parameters are stored in
    # bf16, where an update this small mostly rounds away, the buffer's own
    # bf16 keeps 8 bits of every entry
    m1 = np.asarray(jax.device_get(opt_state["fc1_weight"])).astype(
        np.float32)
    cell["info"](first_step_s=time.perf_counter() - tic)
    tic = time.perf_counter()
    # the reference sees what the step saw: the batch as placed (bf16)
    ref_loss, ref_grad = reference.resnet_loss_and_head_grad(
        before, placed["data"], labels)
    g = np.asarray(ref_grad)
    cos = float(np.sum(-m1 * g) / max(
        np.linalg.norm(m1) * np.linalg.norm(g), 1e-30))
    verdict = {"loss_step0": loss0, "ref_loss": ref_loss,
               "loss_rel_err": abs(loss0 - ref_loss) / ref_loss,
               "loss_tol": LOSS_TOL, "head_grad_cos": cos, "cos_tol": COS_TOL}
    del before, ref_grad
    cell["info"](check=verdict, check_s=time.perf_counter() - tic)

    for _ in range(3):                       # settle: nothing compiles later
        params, opt_state, aux, outs, key = step(
            params, opt_state, aux, placed, key, one)
    jax.block_until_ready(params)
    gc.collect()
    compiles0 = cell["compiles"]()
    tracer = cell["tracer"]
    ann = jax.profiler.TraceAnnotation
    setup_s = time.perf_counter() - cell["t_process"]

    # -- the window: steps dispatched between two block_until_ready ----------
    n_steps = 0
    start = time.perf_counter()
    end = start + cell["seconds"]
    while True:
        now = time.perf_counter()
        if now >= end:
            break
        if tracer is not None and tracer.due(now, end):
            jax.block_until_ready(params)
            tracer.maybe_start(time.perf_counter(), end)
        with ann("bench.train_dispatch"):
            params, opt_state, aux, outs, key = step(
                params, opt_state, aux, placed, key, one)
        n_steps += 1
        # dispatch runs ahead of the device; stay at most two steps ahead
        # so the window's end waits for one step in flight, not a queue
        if n_steps >= 2:
            with ann("bench.train_wait"):
                jax.block_until_ready(prev_outs)
        prev_outs = outs
    jax.block_until_ready(params)
    window_s = time.perf_counter() - start
    if tracer is not None:
        tracer.stop()
    compiled = cell["compiles"]() - compiles0
    if compiled:
        raise RuntimeError(f"{compiled} compilation(s) inside the window")
    # memory_stats() counts live buffers only (396 MB here): the step's
    # temporaries, which hold the activations, come from XLA's own analysis
    temp = step.lower(params, opt_state, aux, placed, key, one).compile() \
        .memory_analysis().temp_size_in_bytes
    loss_end = _loss(outs[0], labels)
    verdict.update(loss_end=loss_end, steps=n_steps)
    ok = (np.isfinite(loss_end) and loss_end < loss0
          and verdict["loss_rel_err"] <= LOSS_TOL and cos >= COS_TOL)
    cell["info"](check=verdict, samples={"steps": n_steps},
                 window_s=window_s)
    img_s = n_steps * batch / window_s / n_chips
    fwd = arith.count_flops(net, data=(1,) + batch_host["data"].shape[1:])
    ctx = {"kind": "train", "steps": n_steps, "window_s": window_s,
           "images_per_s_per_chip": img_s, "fwd_flops_per_image": fwd,
           "batch": batch, "chips": n_chips, "program_temp_bytes": temp}
    e2e = {"setup_s": setup_s, "train_img_s": img_s}
    return bool(ok), n_steps, 0, e2e, ctx
