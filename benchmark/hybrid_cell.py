"""A serving cell of kind ``hybrid``: ``mx.serve.Engine`` over
``mx.models.hybrid_decoder`` (state-space and attention layers, a
per-request state pool beside the paged K/V), driven exactly as a
``serve`` cell is.

Imported from ``serve_cell``, never copied: the driver (``drive``), how a
request's latencies are taken (``latencies_ms``) and which programs the
traffic hits (``programs_for``).  This file's own: the weights
(``make_params``, from ``--seed``), how the engine is built from the
configuration's published keys, ``dims`` for ``arith_hybrid``, and the
comparison that decides ``correct``.

**What ``correct`` compares.**  Nothing is served for the check alone: when
the window has closed, ``sample`` takes requests the WINDOW served (some it
finished, some still decoding in their slots among the other live rows)
and ``check`` teacher-forces each one's prompt and tokens through
``reference_hybrid.py`` (float32, no code shared with ``mxnet_tpu``).  Four
numbers, each with its own limit, set between this configuration's own
readings on the chip and those of ``hybrid_controls.py``'s faulty engines
(PERF.md, PR 29), not imported:

- ``max_regret`` / ``mean_regret``: at every generated position the
  reference's best logit minus its logit of the engine's token.
- ``state_err``: for the requests still running, the relative distance of
  the engine's recurrent states (their slot of the pool, all state-space
  layers) from the reference's after the same tokens.
- ``state_f32_share``: the share of those states' entries that no 16- or
  19-bit float could hold (their lowest 13 mantissa bits are not all
  zero).  Float32 arithmetic leaves nearly all entries so; a pool kept in
  bfloat16, or rounded to it (or to float16, or tf32) at every token,
  leaves none.  On the chip a pool kept in bfloat16 did not move the
  regrets (bfloat16 activations through 40 layers cost more than the
  state's rounding does), so the precision the configuration states is
  probed directly.
"""

import time

import numpy as np

import arith
import reference_hybrid
import traffic as traffic_mod
from serve_cell import _phase_totals, drive, latencies_ms, programs_for

# The limits of ``correct``, each between two readings (my chip runs, PR 29;
# PERF.md section 4): as configured over 7 runs | attention scores 8 times
# too large (``hybrid_controls.py``, ``scale_8x``).
MAX_REGRET_TOL = 0.2        # 0.038-0.077 | 0.71
MEAN_REGRET_TOL = 0.0035    # 0.00028-0.00069 | 0.0315
STATE_ERR_TOL = 0.05        # 0.0161-0.0181 | 0.190
# as configured 0.9997 | a state rounded to 16 bits: 0, by construction
STATE_F32_SHARE_MIN = 0.5
# query and key rows are N(0, QK_GAIN^2 / fan_in): see make_params
QK_GAIN = 4.0


def describe(cfg):
    """The decoder's description from the configuration's published keys."""
    import mxnet_tpu as mx

    return mx.models.hybrid_decoder(
        cfg["vocab_size"], cfg["hidden_size"], cfg["layer_types"],
        num_heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["shared_intermediate_size"],
        mamba_heads=cfg["mamba_n_heads"], mamba_head_dim=cfg["mamba_d_head"],
        mamba_state=cfg["mamba_d_state"], mamba_conv=cfg["mamba_d_conv"],
        mamba_chunk=cfg["mamba_chunk_size"], eps=cfg["rms_norm_eps"],
        embedding_multiplier=cfg["embedding_multiplier"],
        residual_multiplier=cfg["residual_multiplier"],
        attention_multiplier=cfg["attention_multiplier"],
        logits_scaling=cfg["logits_scaling"],
        name=reference_hybrid.NAME)


def make_params(dec, dtype, seed):
    """Random parameters for the decoder's ``param_shapes()``, made on the
    device from the seed in ONE jitted call, in the dtype they are served
    in (the benchmark's own, as ``serve_cell.make_gpt_params`` is: an edit
    of the program's initialiser cannot move what the limits mean).

    Matrices N(0, 1/fan_in); the tied embedding N(0, logits_scaling^2 /
    d_model), so that the logits ``RMSNorm(h) E^T / logits_scaling`` have
    unit spread; norm gains 1 but the FINAL norm's, which is random +-1
    (with +1 everywhere a tied head under an embedding multiplier makes
    every position's own token win by tens of deviations, greedy decoding
    repeats one token and a check can catch nothing); the convolution
    N(0, 1/K) with bias N(0, 0.01); ``A_log = log U(1, 16)``, ``dt_bias``
    the inverse softplus of ``U(0.001, 0.1)`` and ``D = 1``, as the Mamba-2
    reference initialises them, float32 whatever ``dtype`` is.  The seed
    enters as data: every seed runs the same compiled program."""
    import jax
    import jax.numpy as jnp

    shapes = dec.param_shapes()
    dtype = jnp.dtype(dtype)
    emb_std = dec.logits_scaling / float(np.sqrt(dec.d_model))
    n_qk = (dec.num_heads + dec.kv_heads) * dec.head_dim

    def normal(key, shape, std, dt=dtype):
        return (jax.random.normal(key, shape, jnp.float32)
                * np.float32(std)).astype(dt)

    def make(key):
        out = {}
        for i, (name, shape) in enumerate(shapes.items()):
            k = jax.random.fold_in(key, i)
            if name.endswith("ln_f_gamma"):
                out[name] = jnp.where(jax.random.bernoulli(k, 0.5, shape),
                                      1.0, -1.0).astype(dtype)
            elif name.endswith("gamma"):
                out[name] = jnp.ones(shape, dtype)
            elif name.endswith("_D"):
                out[name] = jnp.ones(shape, jnp.float32)
            elif name.endswith("_A_log"):
                out[name] = jnp.log(jax.random.uniform(
                    k, shape, jnp.float32, 1.0, 16.0))
            elif name.endswith("_dt_bias"):
                dt = jax.random.uniform(k, shape, jnp.float32, 1e-3, 1e-1)
                out[name] = dt + jnp.log(-jnp.expm1(-dt))
            elif name.endswith("_conv_bias"):
                out[name] = normal(k, shape, 0.1)
            elif name.endswith("tok_embed_weight"):
                out[name] = normal(k, shape, emb_std)
            elif name.endswith("qkv_weight"):
                w = normal(k, shape, shape[-1] ** -0.5, jnp.float32)
                qk = (jnp.arange(shape[0]) < n_qk)[:, None]
                out[name] = jnp.where(qk, w * np.float32(QK_GAIN),
                                      w).astype(dtype)
            else:
                out[name] = normal(k, shape, shape[-1] ** -0.5)
        return out

    # hardware bit generator: billions of values by threefry take long
    key = jax.random.key(int(seed) % (2 ** 31), impl="unsafe_rbg")
    key = jax.random.fold_in(key, int(seed) >> 31)
    return jax.jit(make)(key)


def build(cfg, seed):
    """(description, params, engine) from the configuration file."""
    reference_hybrid.dims(cfg)          # the keys say what this file builds
    if not cfg["tie_word_embeddings"] or cfg["mamba_proj_bias"] \
            or cfg["attention_bias"] or not cfg["mamba_conv_bias"]:
        raise ValueError("hybrid_cell: tied head, no projection bias, a "
                         "convolution bias: what hybrid_decoder builds")
    dec = describe(cfg)
    params = make_params(dec, cfg["dtype"], seed)
    return dec, params, engine(cfg, dec, params)


def engine(cfg, dec, params):
    import mxnet_tpu as mx

    geo = cfg["engine"]
    return mx.serve.Engine(
        params, symbol=dec, block_size=geo["block_size"],
        num_blocks=geo["num_blocks"], max_batch=geo["max_batch"],
        max_queue=geo["max_queue"], max_model_len=geo["max_model_len"],
        prefill_chunk=geo["prefill_chunk"], tp=geo["tp"])


def dims(cfg):
    """The sizes ``arith_hybrid`` needs, under its own names."""
    kinds = list(cfg["layer_types"])
    heads = cfg["mamba_n_heads"]
    return {"d_model": cfg["hidden_size"], "vocab": cfg["vocab_size"],
            "d_ff": cfg["shared_intermediate_size"],
            "num_heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["hidden_size"] // cfg["num_attention_heads"],
            "m_heads": heads, "m_head_dim": cfg["mamba_d_head"],
            "d_inner": heads * cfg["mamba_d_head"],
            "state": cfg["mamba_d_state"], "conv": cfg["mamba_d_conv"],
            "n_mamba": kinds.count("mamba"),
            "n_attn": kinds.count("attention"),
            "max_batch": cfg["engine"]["max_batch"]}


def sample(out, spec, seed):
    """The requests of the window that ``check`` compares: (finished,
    live), lists of the driver's records.  ``finished`` ended inside the
    window; ``live`` were decoding in their slots when it closed, with at
    least ``min_tokens`` generated.  Of each kind: the one with the
    longest prompt (past ``prefill_chunk`` its state was carried across
    chunk passes), the one with the most tokens generated (drift on one
    state), and others drawn by the seed, ``spec["finished"]`` and
    ``spec["live"]`` in all; where the window finished fewer (the tests'
    window is a second long), running ones make up the number.  Only
    requests of at most ``max_len`` positions and ``max_new`` generated
    ones, so that one compiled reference pass serves them all."""
    def fits(r):
        n = len(r.req.tokens)
        return (not r.failed and spec["min_tokens"] <= n <= spec["max_new"]
                and len(r.req.prompt) + n <= spec["max_len"])

    rng = np.random.default_rng(int(seed) + 1)

    def take(cands, k):
        cands = sorted(cands, key=lambda r: r.due)
        if len(cands) <= k:
            return cands
        first = [max(cands, key=lambda r: len(r.req.prompt)),
                 max(cands, key=lambda r: len(r.req.tokens))]
        picked = {id(r): r for r in first[:k]}
        rest = [r for r in cands if id(r) not in picked]
        for i in rng.permutation(len(rest))[:k - len(picked)]:
            picked[id(rest[i])] = rest[i]
        return sorted(picked.values(), key=lambda r: r.due)

    done = [r for r in out["all"] if r.finish_t is not None and fits(r)
            and out["start"] <= r.finish_t <= out["end"]]
    live = [r for r in out["all"] if r.finish_t is None and fits(r)
            and r.req.status == "running"]        # it holds a slot
    done = take(done, spec["finished"])
    return done, take(live, spec["live"] + spec["finished"] - len(done))


def slot_states(eng, recs):
    """The recurrent states the engine holds for these running requests,
    (M, H, P, N) each, read out of the pool before anything else runs."""
    return [eng._state_ssm[:, eng.blocks.state_slot(r.req.rid)]
            for r in recs]


def check(params, cfg, spec, finished, live, live_states):
    """Teacher-force the sampled requests' own tokens through the float32
    reference and compare (the module's docstring says what)."""
    import jax
    import jax.numpy as jnp

    if len(finished) + len(live) < spec["finished"] + spec["live"]:
        return {"ok": False, "why": f"the window left {len(finished)} "
                f"finished and {len(live)} running requests to compare, "
                f"fewer than {spec['finished']} + {spec['live']}"}
    regrets, spreads = [], []
    dist2 = norm2 = f32_share = 0.0
    for rec, S in zip(finished + live, [None] * len(finished) + live_states):
        ref = reference_hybrid.teacher_force(
            cfg, params, rec.req.prompt, rec.req.tokens,
            pad_to=spec["max_len"], rows=spec["max_new"])
        regrets += ref["regrets"]
        spreads.append(ref["logit_std"])
        if S is None:
            continue
        dist2 += float(jnp.sum(jnp.square(S - ref["states"])))
        norm2 += float(jnp.sum(jnp.square(ref["states"])))
        low = jax.lax.bitcast_convert_type(S, jnp.uint32) & 0x1FFF
        f32_share += float(jnp.mean(low != 0)) / len(live)
    worst, mean = max(regrets), sum(regrets) / len(regrets)
    verdict = {"max_regret": worst, "tol": MAX_REGRET_TOL,
               "mean_regret": mean, "mean_tol": MEAN_REGRET_TOL,
               "finished": len(finished), "live": len(live),
               "tokens": len(regrets),
               "shapes": [[len(r.req.prompt), len(r.req.tokens)]
                          for r in finished + live],
               "reference_logit_std": sum(spreads) / len(spreads)}
    ok = worst <= MAX_REGRET_TOL and mean <= MEAN_REGRET_TOL
    if live:
        verdict.update(state_err=(dist2 / max(norm2, 1e-30)) ** 0.5,
                       state_err_tol=STATE_ERR_TOL,
                       state_f32_share=f32_share,
                       state_f32_share_min=STATE_F32_SHARE_MIN)
        ok = (ok and verdict["state_err"] <= STATE_ERR_TOL
              and f32_share >= STATE_F32_SHARE_MIN)
    return dict(verdict, ok=bool(ok))


def run(cell):
    """One run, as ``serve_cell.run`` makes it, but for the check: build,
    warm every program the traffic can hit, drive, compare a sample of
    what the window served, and hand the readers their context.  Returns
    (correct, attempted, failed, end_to_end, ctx)."""
    import jax

    cfg, mix = cell["config"], cell["traffic"]
    on_tpu = jax.devices()[0].platform == "tpu"
    tic = time.perf_counter()
    dec, params, eng = cell["build"](cfg, cell["seed"])
    status = eng.statusz()
    if on_tpu and status["paged_attention"] != "pallas":
        raise RuntimeError(
            f"decode attention resolved to {status['paged_attention']!r} "
            "on a TPU: the cell must run the Pallas paged kernel")
    if status["state_cache"]["ssm_dtype"] != cfg["state_dtype"]:
        raise RuntimeError(
            f"the recurrent state is {status['state_cache']['ssm_dtype']}, "
            f"the configuration states {cfg['state_dtype']}")
    jax.block_until_ready(params)
    cell["info"](weights_and_engine_s=time.perf_counter() - tic,
                 paged_attention=status["paged_attention"],
                 state_cache=status["state_cache"])

    loop = traffic_mod.loop(mix, cell["seed"], cell["seconds"],
                            cfg["vocab_size"])
    geo = cfg["engine"]
    manifest = programs_for(loop.prompt_len, geo)
    tic = time.perf_counter()
    ready = eng.warmup([{"kind": k, "bucket": b} for k, b in manifest])
    if ready != len(manifest):
        raise RuntimeError(f"warmed {ready} of {len(manifest)} programs")
    cell["info"](programs=len(manifest), warmup_s=time.perf_counter() - tic)

    marks = {}

    def on_window():
        marks["compiles"] = cell["compiles"]()
        marks["phases"] = _phase_totals(eng)
        marks["setup_s"] = time.perf_counter() - cell["t_process"]

    out = drive(eng, loop, cell["seconds"], tracer=cell["tracer"],
                on_window=on_window)
    if cell["tracer"] is not None:
        cell["tracer"].stop()
    compiled = cell["compiles"]() - marks["compiles"]
    phases_end = _phase_totals(eng)
    stats = eng.stats()
    ran = {(e["kind"], int(e["bucket"])) for e in eng.manifest()}
    if compiled or not ran <= set(manifest):
        raise RuntimeError(
            f"{compiled} compilation(s) inside the window; programs run "
            f"but not warmed: {sorted(ran - set(manifest))}")
    finished, live = sample(out, mix["check"], cell["seed"])
    live_states = slot_states(eng, live)

    recs = out["records"]
    n_failed = sum(r.failed for r in recs)
    ttft, tpot = latencies_ms(out)
    e2e = {"setup_s": marks["setup_s"],
           "out_tok_s": out["tokens"] / out["window_s"],
           "ttft_ms_p90": arith.percentile(ttft, 90),
           "tpot_ms_p90": arith.percentile(tpot, 90)}
    cell["info"](samples={"ttft": len(ttft), "tpot": len(tpot),
                          "tokens": out["tokens"],
                          "steps": len(out["steps"])},
                 window_s=out["window_s"], due_in_window=len(recs),
                 finished_in_window=out["finished_in_window"],
                 unfinished=sum(r.finish_t is None for r in recs),
                 no_token_yet=sum(r.first_t is None and not r.failed
                                  for r in recs),
                 queue_at_end=stats.queue_depth, running_at_end=stats.running,
                 preemptions=stats.preemptions,
                 ttft_ms_p50=arith.percentile(ttft, 50),
                 tpot_ms_p50=arith.percentile(tpot, 50),
                 out_tok_s=e2e["out_tok_s"])
    phases = None
    if marks["phases"] and phases_end:
        phases = {k: phases_end[k] - marks["phases"].get(k, 0.0)
                  for k in phases_end}
    ctx = {"kind": "hybrid", "hybrid": dims(cfg), "steps": out["steps"],
           "late_ms": [r.late * 1e3 for r in recs],
           "phase_seconds": phases, "window": out, "stats": stats}
    eng.shutdown()                  # the pools' room goes to the reference
    tic = time.perf_counter()
    verdict = check(params, cfg, mix["check"], finished, live, live_states)
    cell["info"](check=verdict, check_s=time.perf_counter() - tic)
    return verdict["ok"], len(recs), n_failed, e2e, ctx
