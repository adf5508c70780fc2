#!/usr/bin/env python
"""On-chip smoke: the serve engine and the ResNet-50 trainer start, run
and give right answers on the TPU this process holds.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # one host, four chips (tp=4, dp=4)

Two phases through the entry points a user calls, in ONE process (a
chip belongs to one process; nothing here spawns another):

serve   ``mx.serve.Engine`` over ``mx.models.gpt`` fronted by an
        in-process ``fleet.ReplicaServer``, answering ``POST /generate``
        over loopback.  Widths are those published for Mistral-7B-v0.3
        (https://huggingface.co/mistralai/Mistral-7B-v0.3, config.json:
        hidden 4096, 32 query / 8 key-value heads of 128, SwiGLU 14336,
        vocab 32768, untied head, rope).  Cut to size: 16 of the 32
        layers — 32 layers of bf16 weights do not fit one 16 GB chip —
        and random weights from a seed; the gpt() projections carry
        (zero) biases the published model does not have.
train   ResNet-50 through ``mx.parallel.ShardedTrainer`` as ``bench.py``
        builds it: 224 px, NHWC, space-to-depth stem, bf16, batch 128
        per chip, ``local_mesh("dp")``.

The script refuses to run without a TPU and exits non-zero if any check
fails.  Its standard output is two lines, each one JSON object: the
report (``{"report": {...}}``: versions, the model cut, per-phase set-up
and run seconds, the compile cache, the audited programs) and then, as
the LAST line, the verdict the driver parses — exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``
with the device as jax reports it.  No rate it prints is a benchmark
result.
"""

import argparse
import concurrent.futures
import json
import re
import sys
import time
import urllib.request

# -- sizes --------------------------------------------------------------------
# weights: 16 x 218.1 M + 2 x 134.2 M params = 3.76 G params = 7.5 GB bf16;
# KV cache: 2049 blocks x 16 tokens x 16 layers x 2 x 8 x 128 x 2 B = 2.1 GB
# (every one of max_batch=8 requests can reach max_model_len: no preemption,
# so the programs traffic hits are the ones the manifest warms)
FULL_SERVE = dict(
    vocab=32768, d_model=4096, num_heads=32, kv_heads=8, d_ff=14336,
    num_layers=16, max_model_len=4096, block_size=16, num_blocks=2049,
    max_batch=8, prefill_chunk=2048, dtype="bfloat16", max_new=64,
    # prompt lengths (tokens): one whole-prompt prefill at the chunk
    # threshold, one prompt past it (two chunks), a shared prefix of whole
    # blocks with per-request suffixes, and short distinct prompts
    whole=2048, chunked=3600, prefix=64, suffix=40, short=100)
FULL_TRAIN = dict(num_layers=50, image_hw=224, batch_per_chip=128,
                  dtype="bfloat16", steps=5)

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def _compile_requests():
    """Programs handed to the XLA compiler so far (persistent-cache hits
    included): the telemetry bridge counts jax's own monitoring event."""
    import mxnet_tpu as mx

    return mx.telemetry.registry().counter(
        "mxtpu_jax_compile_total", "jax compile-path events",
        ("event",)).labels(event=BACKEND_COMPILE).value


def _audit_program_text(name, text, audit):
    """Record one compiled program in ``audit``; fail on a non-scalar f64
    value (a TPU emulates f64) in StableHLO (``tensor<8x16xf64>``) or HLO
    (``f64[8,16]``) text.  The i64 count is reported only."""
    f64 = re.findall(r"tensor<[0-9x]+xf64>|f64\[[0-9]", text)
    if f64:
        raise AssertionError(
            f"program {name} carries {len(f64)} non-scalar f64 values, "
            f"e.g. {f64[0]!r}")
    audit[name] = {
        "i64_values": len(re.findall(r"tensor<[0-9x]*xi64>|s64\[", text)),
        "tpu_custom_calls": text.count("tpu_custom_call")}


def make_gpt_params(net, seq_len, dtype, seed):
    """Random parameters for a gpt() symbol, made on the device from a
    seed (the full model is 3.8 G values: a host generator would take
    minutes and a 15 GB f32 staging copy).  Matrices are N(0, 1/fan_in)
    so activations stay O(1) through the stack; norm gains are 1,
    biases 0."""
    import jax
    import jax.numpy as jnp

    arg_shapes, _, _ = net.infer_shape(data=(1, seq_len),
                                       softmax_label=(1, seq_len))
    key = jax.random.PRNGKey(seed)
    params = {}
    for i, (name, shape) in enumerate(zip(net.list_arguments(), arg_shapes)):
        if name in ("data", "softmax_label"):
            continue
        if name.endswith("gamma"):
            params[name] = jnp.ones(shape, dtype)
        elif name.endswith("weight"):
            w = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            params[name] = (w * shape[-1] ** -0.5).astype(dtype)
        else:
            params[name] = jnp.zeros(shape, dtype)
    return params


def _post_generate(url, prompt, max_new, request_id):
    body = json.dumps({"prompt": [int(t) for t in prompt],
                       "max_new_tokens": max_new,
                       "request_id": request_id}).encode()
    req = urllib.request.Request(url + "/generate", data=body,
                                 headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=900) as resp:
            payload = json.loads(resp.read())
    except OSError as e:
        # a replica whose engine step raised tears its socket down; the
        # reason is in the flight ring, not on the wire
        from mxnet_tpu.telemetry import flight

        errors = [ev.get("error") for ev in flight.recorder().events()
                  if ev["kind"] == "error"]
        raise RuntimeError(f"{request_id}: /generate failed ({e}); engine "
                           f"errors: {errors[-2:]}") from e
    if "tokens" not in payload:
        raise AssertionError(f"{request_id}: no tokens in {payload}")
    return payload["tokens"]


def _written_blocks(k_layer):
    """Ids of the blocks of one layer's K pool the traffic wrote."""
    import jax.numpy as jnp
    import numpy as np

    written = np.flatnonzero(np.asarray(
        jnp.any(k_layer != 0, axis=(1, 2, 3))))
    if written.size < 8:
        raise AssertionError("traffic left fewer than 8 written KV blocks")
    return written


def _kernel_vs_oracle(eng, cfg, seed):
    """The compiled Pallas paged kernel against the ``impl="jnp"`` oracle
    on the cache the traffic just wrote.  Returns (max abs error,
    tolerance).

    Tolerance: 2^-6 of the largest |V| entry.  Both paths round the
    softmax weights and the output to the cache dtype (bf16: 2^-8
    relative); the oracle also rounds the raw q.k scores to bf16 before
    the softmax where the kernel keeps f32, which moves each weight by
    at most one bf16 ulp of a score.  The output is a convex combination
    of V rows, so the paths may differ by a few bf16 ulps of max|V| — 4
    ulps = 2^-6 — and a wrong block, head or mask moves it by O(max|V|).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.ops.attention import paged_attention

    layer = cfg["num_layers"] - 1
    k_cache, v_cache = eng._cache_k[layer], eng._cache_v[layer]
    written = _written_blocks(k_cache)
    rng = np.random.RandomState(seed)
    width = eng.table_width
    batch = cfg["max_batch"]
    tables = rng.choice(written, size=(batch, width)).astype(np.int32)
    cap = width * cfg["block_size"]
    # context lengths from one token to the whole table, block-unaligned
    ctx = np.array([1, cfg["block_size"] + 1, cap // 7, cap // 3,
                    cap // 2 + 5, cap - 1, cap, 37][:batch], np.int32)
    ctx = np.minimum(np.maximum(ctx, 1), cap)
    q = jnp.asarray(rng.standard_normal(
        (batch, cfg["num_heads"], cfg["d_model"] // cfg["num_heads"])),
        k_cache.dtype)
    outs = {}
    for impl in ("pallas", "jnp"):
        fn = jax.jit(lambda q, k, v, t, c, impl=impl: paged_attention(
            q, k, v, t, c, impl=impl))
        outs[impl] = np.asarray(
            fn(q, k_cache, v_cache, jnp.asarray(tables), jnp.asarray(ctx))
        ).astype(np.float32)
    err = float(np.max(np.abs(outs["pallas"] - outs["jnp"])))
    vmax = float(jnp.max(jnp.abs(v_cache.astype(jnp.float32))))
    if not np.isfinite(outs["pallas"]).all():
        raise AssertionError("paged kernel produced non-finite values")
    return err, 2.0 ** -6 * vmax


def _span_vs_oracle(eng, cfg, seed):
    """The span attention as the chunk program calls it (the Mosaic
    kernel on the chip; per head shard under the engine's mesh at tp >
    1) against plain float32 attention, on the cache the traffic just
    wrote: the second pass of the prompt longer than ``prefill_chunk``,
    a bucket of ``prefill_chunk`` rows from position ``prefill_chunk``
    of which ``chunked - prefill_chunk`` are real, over the whole table.
    Returns (max abs error over the real rows, tolerance, branch).

    Tolerance: ``_kernel_vs_oracle``'s, 2^-6 of the largest |V| entry
    (probabilities and output rounded to bf16 on the one side only)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.ops.attention import masked_attention, score_scale
    from mxnet_tpu.serve.programs import _head_shard_kw

    layer = cfg["num_layers"] - 1
    ck, cv = eng._cache_k, eng._cache_v
    written = _written_blocks(ck[layer])
    rng = np.random.RandomState(seed)
    table = jnp.asarray(rng.choice(written, size=eng.table_width), jnp.int32)
    rows, start = cfg["prefill_chunk"], cfg["prefill_chunk"]
    real = cfg["chunked"] - start
    Hq, Hkv = cfg["num_heads"], cfg["kv_heads"]
    Dh = cfg["d_model"] // Hq
    S = eng.table_width * cfg["block_size"]
    scale = score_scale(Dh)
    q = jnp.asarray(rng.standard_normal((rows, Hq, Dh)), ck.dtype)
    kw = _head_shard_kw(eng._shardings)

    def view(c, table):
        return c[layer, table].reshape(S, Hkv, Dh)

    @jax.jit
    def served(q, ck, cv, table):
        return masked_attention(q, view(ck, table), view(cv, table), start,
                                scale, n_valid=real, **kw)

    @jax.jit
    def oracle(q, ck, cv, table):
        f32 = jnp.float32
        k, v = view(ck, table).astype(f32), view(cv, table).astype(f32)
        qg = q.astype(f32).reshape(rows, Hkv, Hq // Hkv, Dh)
        with jax.default_matmul_precision("highest"):
            sc = jnp.einsum("qkgd,skd->kgqs", qg, k) * scale
            keep = (jnp.arange(S)[None, :]
                    <= start + jnp.arange(rows)[:, None])
            pr = jax.nn.softmax(jnp.where(keep, sc, -jnp.inf), axis=-1)
            return jnp.einsum("kgqs,skd->qkgd", pr, v)

    got = np.asarray(served(q, ck, cv, table)).astype(np.float32)
    want = np.asarray(oracle(q, ck, cv, table))
    if not np.isfinite(got).all():
        raise AssertionError("span attention produced non-finite values")
    vmax = float(jnp.max(jnp.abs(cv[layer].astype(jnp.float32))))
    return (float(np.max(np.abs(got[:real] - want[:real]))),
            2.0 ** -6 * vmax, eng._span_impl(rows, S))


def serve_phase(cfg, tp=1, reference_tokens=None, seed=0):
    """Build the engine, warm exactly the programs the traffic will hit,
    answer nine requests over loopback HTTP, check the answers.  Returns
    the phase's report dict (incl. ``tokens`` per request)."""
    import jax
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.serve import engine as engine_mod

    on_chip = jax.devices()[0].platform == "tpu"
    t_setup = time.perf_counter()
    net = mx.models.gpt(
        cfg["vocab"], cfg["max_model_len"], num_layers=cfg["num_layers"],
        d_model=cfg["d_model"], num_heads=cfg["num_heads"],
        d_ff=cfg["d_ff"], norm="rmsnorm", mlp="swiglu", pos_embed="rope",
        tie_embeddings=False, kv_heads=cfg["kv_heads"])
    params = make_gpt_params(net, cfg["max_model_len"], cfg["dtype"], seed)
    param_bytes = sum(v.nbytes for v in params.values())
    eng = mx.serve.Engine(
        params, symbol=net, block_size=cfg["block_size"],
        num_blocks=cfg["num_blocks"], max_batch=cfg["max_batch"],
        max_queue=4 * cfg["max_batch"], max_model_len=cfg["max_model_len"],
        prefill_chunk=cfg["prefill_chunk"], tp=tp)
    if tp > 1:
        # the engine placed its own shards: let go of the unsharded
        # originals (dropped, not deleted — a replicated shard may alias
        # the original's buffer on device 0)
        params.clear()
    cache_bytes = eng.kv_cache_stats()["bytes_total"]
    paged_impl = eng.statusz()["paged_attention"]
    if on_chip and paged_impl != "pallas":
        raise AssertionError(
            f"decode attention resolved to {paged_impl!r} on a TPU: the "
            "smoke's cache geometry must run the Pallas kernel")

    # -- the requests ---------------------------------------------------------
    rng = np.random.RandomState(seed)
    draw = lambda n: rng.randint(0, cfg["vocab"], n)
    prefix = draw(cfg["prefix"])
    shared = lambda: np.concatenate([prefix, draw(cfg["suffix"])])
    seed_prompt = shared()
    batch = ([("whole", draw(cfg["whole"])), ("chunked", draw(cfg["chunked"]))]
             + [(f"shared{i}", shared()) for i in range(3)]
             + [(f"short{i}", draw(cfg["short"])) for i in range(3)])
    max_new = cfg["max_new"]

    # -- warm the programs these requests hit, and no others ------------------
    bucket = engine_mod._next_bucket
    L, chunk = cfg["max_model_len"], cfg["prefill_chunk"]
    manifest = (
        [("decode", b) for b in eng._bucket_ladder(cfg["max_batch"])]
        + [("prefill", bucket(n, L)) for n in (
            seed_prompt.size, cfg["whole"], cfg["short"])]
        # a chunk shrinks by the decode slots running beside it (<= 7)
        + [("chunk", bucket(n, eng._chunk_cap())) for n in (
            cfg["suffix"], chunk, chunk - cfg["max_batch"],
            cfg["chunked"] - chunk, cfg["chunked"] - chunk
            + cfg["max_batch"])])
    manifest = sorted(set(manifest))
    ready = eng.warmup([{"kind": k, "bucket": b} for k, b in manifest])
    if ready != len(manifest):
        raise AssertionError(f"warmed {ready} of {len(manifest)} programs")
    audit = {}
    for kind, b in manifest:
        # straight from the program cache: Engine._program would record
        # the lookup as traffic
        compiled = engine_mod._STEP_CACHE[(eng._spec_key(), kind, b)]
        _audit_program_text(f"serve.{kind}{b}", compiled.as_text(), audit)
        calls = audit[f"serve.{kind}{b}"]["tpu_custom_calls"]
        if on_chip and kind == "decode" and calls < cfg["num_layers"]:
            raise AssertionError(
                f"decode{b} holds {calls} tpu_custom_call(s) for "
                f"{cfg['num_layers']} layers: the paged kernel did not "
                "run compiled")
        if (on_chip and kind != "decode" and b >= cfg["prefill_chunk"]
                and calls < cfg["num_layers"]):
            raise AssertionError(
                f"{kind}{b} holds {calls} tpu_custom_call(s) for "
                f"{cfg['num_layers']} layers: the span kernel did not "
                "run compiled")

    server = mx.fleet.ReplicaServer(eng, replica_id="chip-smoke").start()
    try:
        # first contact, alone: publishes the shared prefix's blocks (so
        # the concurrent batch below is certain to hit them) and pays the
        # one-time compile of the small host-side helper programs
        tokens = {"seed": _post_generate(server.url, seed_prompt, max_new,
                                         "seed")}
        setup_s = time.perf_counter() - t_setup
        compiles_warm = _compile_requests()

        t_run = time.perf_counter()
        with concurrent.futures.ThreadPoolExecutor(len(batch)) as pool:
            futs = {name: pool.submit(_post_generate, server.url, p,
                                      max_new, name) for name, p in batch}
            tokens.update({name: f.result() for name, f in futs.items()})
        run_s = time.perf_counter() - t_run
        compiles_after = _compile_requests() - compiles_warm

        # -- checks -----------------------------------------------------------
        for name, toks in tokens.items():
            if len(toks) != max_new or not all(
                    0 <= t < cfg["vocab"] for t in toks):
                raise AssertionError(f"{name}: bad tokens {toks[:8]}...")
        anomalies = sum(
            s["value"] for s in mx.telemetry.registry().snapshot().get(
                "mxtpu_numeric_anomalies_total", {}).get("samples", []))
        if anomalies:
            raise AssertionError(
                f"{anomalies} dispatch(es) returned non-finite logits")
        if compiles_after:
            raise AssertionError(
                f"{compiles_after} compilation(s) after warm-up")
        ran = {(e["kind"], int(e["bucket"])) for e in eng.manifest()}
        want = {("prefill", bucket(cfg["whole"], L)),
                ("chunk", bucket(chunk, eng._chunk_cap())),
                ("chunk", bucket(cfg["suffix"], eng._chunk_cap())),
                ("decode", cfg["max_batch"])}
        if not want <= ran:
            raise AssertionError(f"programs not exercised: {want - ran}")
        if not ran <= set(manifest):
            raise AssertionError(
                f"traffic ran unwarmed programs: {ran - set(manifest)}")
        prefix_stats = eng.blocks.prefix_stats()
        if prefix_stats["hits"] < 3:
            raise AssertionError(f"no radix hits: {prefix_stats}")
        if eng.scheduler.preemptions:
            raise AssertionError(
                f"{eng.scheduler.preemptions} preemptions: cache undersized")
        report = {
            "tp": tp, "paged_attention": paged_impl,
            "params_gb": round(param_bytes / 1e9, 3),
            "kv_cache_gb": round(cache_bytes / 1e9, 3),
            "programs_warmed": len(manifest),
            "requests": len(tokens), "new_tokens": max_new * len(tokens),
            "prefix_hits": prefix_stats["hits"],
            "setup_s": round(setup_s, 2), "run_s": round(run_s, 2),
            "compiles_after_warmup": int(compiles_after),
            "programs": audit, "tokens": tokens}
        if tp == 1:
            # (a plain jit cannot partition the kernel over a sharded
            # cache; at tp > 1 the token agreement below covers it)
            err, tol = _kernel_vs_oracle(eng, cfg, seed)
            if not err <= tol:
                raise AssertionError(
                    f"paged kernel vs jnp oracle: max abs error {err} > "
                    f"tolerance {tol}")
            report.update(kernel_max_abs_err=err, kernel_tol=tol)
        else:
            report["sharding"] = _check_sharded(eng)
        # the prompt longer than prefill_chunk went through the chunk
        # program's span attention: the same call against float32
        err, tol, branch = _span_vs_oracle(eng, cfg, seed)
        if on_chip and branch != "kernel":
            raise AssertionError(
                f"span attention resolved to {branch!r} on a TPU: the "
                "smoke's chunk must run the Pallas kernel")
        if not err <= tol:
            raise AssertionError(
                f"span attention ({branch}) vs float32 oracle: max abs "
                f"error {err} > tolerance {tol}")
        report.update(span_attention=branch, span_max_abs_err=err,
                      span_tol=tol)
        if reference_tokens is not None:
            report.update(_token_agreement(tokens, reference_tokens))
    finally:
        server.stop()                    # shuts the engine down too
        for v in params.values():
            if not v.is_deleted():
                v.delete()
    return report


def _check_sharded(eng):
    """Every device of the tp mesh holds a shard of the KV cache and of
    every sharded parameter."""
    n = eng.tp
    cache_devs = {s.device for s in eng._cache_k.addressable_shards}
    if len(cache_devs) != n or any(
            s.data.shape[3] * n != eng._cache_k.shape[3]
            for s in eng._cache_k.addressable_shards):
        raise AssertionError("KV cache is not head-sharded over every chip")
    split = 0
    for name, arr in eng.params.items():
        if len({s.device for s in arr.addressable_shards}) != n:
            raise AssertionError(f"{name} is not on every chip")
        split += arr.addressable_shards[0].data.size * n == arr.size
    if not split:
        raise AssertionError("no parameter is split across the chips")
    return {"devices": n, "split_params": int(split),
            "params": len(eng.params)}


def _token_agreement(tokens, reference):
    """Greedy tokens of a tp>1 run against the tp=1 run of the same
    requests.  With random weights and a bf16 head over 32 k tokens the
    two largest logits often sit within a bf16 ulp of each other, so an
    all-reduce's different summation order legitimately flips an argmax
    now and then and the sequences part ways from there (on the chip the
    tp=4 and tp=1 streams agreed for 10 to 64 tokens; my chip run, PR 21).
    What sharding bugs do is different: a wrong head split
    or a missing all-reduce changes every logit by O(1), so no first
    token survives.  The check is therefore on first tokens — at least
    two thirds must agree (chance agreement is 1 in 32768 each) — and the
    agreeing prefix lengths are reported."""
    first = sum(tokens[k][0] == reference[k][0] for k in reference)
    prefix = {}
    for k in reference:
        n = 0
        while (n < len(reference[k]) and n < len(tokens[k])
               and tokens[k][n] == reference[k][n]):
            n += 1
        prefix[k] = n
    if 3 * first < 2 * len(reference):
        raise AssertionError(
            f"only {first} of {len(reference)} first tokens agree with "
            f"the tp=1 run (agreeing prefixes: {prefix})")
    return {"first_tokens_agree": f"{first}/{len(reference)}",
            "agreeing_prefix_len": prefix}


def train_phase(cfg, seed=0):
    """ResNet-50 on ``ShardedTrainer`` over every local chip: a few
    steps on one synthetic batch, loss finite each step, parameters
    moved, nothing compiled after step 1."""
    import jax
    import numpy as np

    import mxnet_tpu as mx

    t_setup = time.perf_counter()
    mx.random.seed(seed)       # the initializer's and the step's RNGs
    n_chips = len(jax.devices())
    hw, batch = cfg["image_hw"], cfg["batch_per_chip"] * n_chips
    net = mx.models.resnet(num_classes=1000, num_layers=cfg["num_layers"],
                           image_shape=(3, hw, hw), layout="NHWC",
                           stem="s2d")
    shapes = {"data": (batch, hw // 2, hw // 2, 12),
              "softmax_label": (batch,)}
    trainer = mx.parallel.ShardedTrainer(
        net, shapes, mesh=mx.parallel.local_mesh("dp"), optimizer="sgd",
        optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
        initializer=mx.initializer.Xavier(rnd_type="gaussian",
                                          factor_type="in", magnitude=2),
        dtype=cfg["dtype"])
    rng = np.random.RandomState(seed)
    data = {"data": rng.uniform(-1, 1, shapes["data"]).astype(np.float32),
            "softmax_label": rng.randint(0, 1000, batch).astype(np.float32)}
    labels = data["softmax_label"].astype(np.int64)
    probe = sorted(n for n in trainer.params if n.endswith("weight"))[0]
    before = np.asarray(trainer.params[probe]).astype(np.float32)
    for name, arr in trainer.params.items():
        if len(arr.sharding.device_set) != n_chips:
            raise AssertionError(f"{name} is not on every chip")

    audit, losses = {}, []
    setup_s = run_s = 0.0
    for step in range(cfg["steps"]):
        tic = time.perf_counter()
        probs = np.asarray(trainer.step(data)[0]).astype(np.float32)
        loss = float(np.mean(-np.log(np.maximum(
            probs[np.arange(batch), labels], 1e-30))))
        if not np.isfinite(probs).all() or not np.isfinite(loss):
            raise AssertionError(f"step {step}: non-finite loss {loss}")
        losses.append(round(loss, 4))
        if step == 0:
            setup_s = time.perf_counter() - t_setup
            spec = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                  sharding=a.sharding)
            _audit_program_text("train.step", trainer._train_step.lower(
                *jax.tree_util.tree_map(spec, (
                    trainer.params, trainer.opt_state, trainer.aux,
                    trainer._place_batch(data), trainer._key)),
                np.float32(1.0)).as_text(), audit)
            compiles_warm = _compile_requests()
        else:
            run_s += time.perf_counter() - tic
    compiles_after = _compile_requests() - compiles_warm
    if compiles_after:
        raise AssertionError(f"{compiles_after} compilation(s) after step 1")
    after = np.asarray(trainer.params[probe]).astype(np.float32)
    if not np.isfinite(after).all() or np.array_equal(before, after):
        raise AssertionError(f"{probe} did not change (or went non-finite)")
    return {"model": f"resnet{cfg['num_layers']}", "attention": None,
            "dp": n_chips, "batch": batch, "image_hw": hw,
            "dtype": cfg["dtype"], "steps": cfg["steps"], "losses": losses,
            "setup_s": round(setup_s, 2), "run_s": round(run_s, 2),
            "compiles_after_step1": int(compiles_after), "programs": audit}


def verdict_line(devices):
    """The last line of standard output: exactly the keys ``ok`` and
    ``device`` (``platform``, ``kind``, ``count``) — the driver refuses
    any other shape, so everything else goes on the report line."""
    return json.dumps({
        "ok": True,
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": len(devices)}})


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: also serve at tp=4 (checked against tp=1) "
                         "and train at dp=4")
    args = ap.parse_args()

    import os

    # every bucket program returns a logits-finite flag the engine counts
    os.environ["MXTPU_NUMERIC_WATCH"] = "1"
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke.py: no TPU — jax reports platform "
                 f"{dev.platform!r}; this script measures nothing on a CPU")
    if len(jax.devices()) != args.chips:
        sys.exit(f"chip_smoke.py: --chips {args.chips} but jax reports "
                 f"{len(jax.devices())} device(s)")

    import jaxlib

    import mxnet_tpu as mx
    from mxnet_tpu import flops

    mx.telemetry.enable()
    flops.peak_flops_per_chip(dev)       # an unlisted device_kind raises
    cache = mx.aot.cache.active()
    if cache is None:
        sys.exit("chip_smoke.py: the persistent compile cache is off")

    serve = serve_phase(FULL_SERVE)
    reference = serve.pop("tokens")
    out = {"serve": serve}
    if args.chips > 1:
        out["serve_tp"] = serve_phase(FULL_SERVE, tp=args.chips,
                                      reference_tokens=reference)
        out["serve_tp"].pop("tokens")
    out["train"] = train_phase(FULL_TRAIN)

    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None
    cache_stats = cache.statusz()
    cut = {k: FULL_SERVE[k] for k in (
        "vocab", "d_model", "num_heads", "kv_heads", "d_ff", "num_layers",
        "max_model_len", "block_size", "num_blocks", "max_batch", "dtype")}
    print(json.dumps({"report": {
        "platform": dev.platform, "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": libtpu_version},
        "model": dict(cut, source="Mistral-7B-v0.3 widths, 16 of 32 layers, "
                                  "random weights"),
        "peak_bytes_in_use": dev.memory_stats().get("peak_bytes_in_use"),
        "compile_cache": {"dir": cache_stats["dir"], **{
            k: int(cache_stats[k]) for k in ("entries", "hits", "misses")}},
        **out}}))
    print(verdict_line(jax.devices()), flush=True)


if __name__ == "__main__":
    main()
