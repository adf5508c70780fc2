"""A serving cell of kind ``branch``: ``mx.serve.Engine`` over
``mx.models.branch_decoder`` (every layer a Mamba-2 mixer in state groups,
an attention without positions, or routed experts in a latent width; this
chip's share of the experts and of the vocabulary), driven exactly as a
``serve`` cell is.

Imported, never copied: the driver, the latencies and the programs the
traffic hits (``serve_cell``), how the window's requests are sampled and
how the running ones' states are read (``hybrid_cell.sample``,
``slot_states``).  This file's own: the weights (``make_params``, from
``--seed``), how the engine is built from the configuration's source keys,
``dims`` for ``arith_branch``, and the comparison that decides
``correct``.

**What ``correct`` compares.**  Nothing is served for the check alone: when
the window has closed, requests the WINDOW served (some it finished, some
still decoding in their slots) are teacher-forced through
``reference_branch.py`` (float32, no code shared with ``mxnet_tpu``), given
the same share of experts and vocabulary.  Six numbers are HELD, each to a
limit of its own, set between this configuration's own readings on the chip
and those of ``branch_controls.py``'s faulty references (PERF.md, PR 36);
``state_err`` over all layers is reported beside them and decides nothing:

- ``max_regret`` / ``mean_regret``: at every generated position the
  reference's best logit minus its logit of the engine's token.  A router
  is a discontinuity (22 picks of 512 by sigmoids that lie close: bfloat16
  activations flip picks against the float32 reference, ``pick_flips``),
  so these tell a wrong MODEL, not a precision: among them the attention
  layer's head mapping (``kv_swapped``), which no other number sees.
- ``state_err_first``: for the requests still running, the relative
  distance of the engine's recurrent states (their slot of the pool) from
  the reference's after the same tokens, over the FIRST state layer
  (layer 0: nothing routes in front of it): the grouped scan of the
  prompt and every grouped update since, their rounding and nothing else.
- ``state_err`` (reported, with ``state_err_by_layer``; NOT held): the
  same distance over all 5 state layers.  A state layer behind a routed
  block inherits that block's flipped picks (its input is 1-26 % from
  the teacher-forced reference's, ``u_err_by_layer``), so this reads the
  flips: 0.08-0.20 over 22 seeds, 0.29 in the last layer, with no
  ceiling a limit could stand on.  A wrong MODEL in a later state layer
  moves the regrets (``one_group`` 1.35, ``norm_whole`` 1.08 against a
  ``mean_regret`` limit of 0.13).
- ``state_f32_share``: the share of those states' entries that no 16- or
  19-bit float could hold; a pool kept in, or rounded to, bfloat16 reads 0.
- ``ffn_err``: the routed blocks' outputs of the window's LAST decode pass
  and LAST prefill pass, as those compiled programs left them in the
  engine's probe, against the reference's block on the same inputs (rows
  whose last pick the reference decides by under ``GAP_MIN`` left out):
  the router's scoring and bias, the 22 weights and their scale, the
  latent pair, the squared ReLU, the accumulation's precision.
- ``u_err``: the engine's input to the FIRST routed block (layer 1) at the
  newest position of the sampled running requests against the teacher-
  forced reference's: in front of it lie the embedding and Mamba-2 layer
  0 and no router, so nothing can flip; it tells the state groups and the
  gated norm's groups.
"""

import time

import numpy as np

import arith
import reference_branch
import traffic as traffic_mod
from hybrid_cell import sample, slot_states
from moe_cell import _distance
from serve_cell import _phase_totals, drive, latencies_ms, programs_for

# The limits of ``correct``, each between two readings (my chip runs, PR 36;
# PERF.md section 4): as configured over 22 seeds | the nearest control's
# lowest of three seeds (``branch_controls.py``).
MAX_REGRET_TOL = 3.5         # 1.13-2.41 | 4.03 (relu_plain), 4.13 (norm_whole)
MEAN_REGRET_TOL = 0.13       # 0.051-0.061 | 0.169 (kv_swapped), 0.264 (no_bias)
STATE_ERR_FIRST_TOL = 0.03   # 0.0047-0.0055 | 1.16 (one_group)
STATE_F32_SHARE_MIN = 0.5    # 0.9997 | a pool rounded to 16 bits: 0
FFN_ERR_TOL = 0.0078         # 0.00437-0.00441 | 0.0138 (acc_bf16)
U_ERR_TOL = 0.03             # 0.0050-0.0056 | 0.205 (norm_whole)
# a probed row enters ffn_err only if the reference decides its last pick
# by more than this (selection scores; the engine's float32 router differs
# from the reference's by 1e-6 on the same input)
GAP_MIN = 2e-5
# query and key rows are N(0, QK_GAIN^2 / fan_in): scores of spread 2
QK_GAIN = 2.0 ** 0.5
# the selection bias's spread
BIAS_STD = 0.01


def describe(cfg):
    """The decoder's description from the configuration's source keys."""
    import mxnet_tpu as mx

    d = reference_branch.dims(cfg)
    return mx.models.branch_decoder(
        d["V"], d["D"], d["kinds"], num_heads=d["Hq"], kv_heads=d["Hkv"],
        head_dim=d["Dh"], mamba_heads=d["H"], mamba_head_dim=d["P"],
        mamba_state=d["N"], mamba_groups=d["G"], mamba_conv=d["K"],
        mamba_chunk=d["chunk"], num_experts=d["E"], top_k=d["k"],
        expert_ff=d["F"], shared_ff=d["Fs"], latent=d["latent"],
        routed_scale=d["scale"], experts_held=(d["offset"], d["held"]),
        eps=d["eps"], name=reference_branch.NAME)


def make_params(dec, dtype, seed):
    """Random parameters for the decoder's ``param_shapes()``, made on the
    device from the seed in ONE jitted call, in the dtype they are served
    in (the benchmark's own: an edit of the program's initialiser cannot
    move what the limits mean).  Matrices N(0, 1/fan_in) (an expert's fan
    in is its rows' width), norm gains 1; query and key rows times
    ``QK_GAIN``; the router's rows N(0, 1/fan_in) (logits of spread 1)
    and its selection bias N(0, ``BIAS_STD``^2), float32; the convolution
    N(0, 1/K) with bias N(0, 0.01); ``A_log = log U(1, 16)``, ``dt_bias``
    the inverse softplus of ``U(0.001, 0.1)`` and ``D = 1``, as the
    Mamba-2 reference initialises them, float32 whatever ``dtype`` is.
    The seed enters as data."""
    import jax
    import jax.numpy as jnp

    shapes = dec.param_shapes()
    dtype = jnp.dtype(dtype)
    n_qk = (dec.num_heads + dec.kv_heads) * dec.head_dim

    def normal(key, shape, std, dt=dtype):
        return (jax.random.normal(key, shape, jnp.float32)
                * np.float32(std)).astype(dt)

    def make(key):
        out = {}
        for i, (name, shape) in enumerate(shapes.items()):
            k = jax.random.fold_in(key, i)
            if name.endswith("gamma"):
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
            elif name.endswith("_router_bias"):
                out[name] = normal(k, shape, BIAS_STD, jnp.float32)
            elif name.endswith("qkv_weight"):
                w = normal(k, shape, shape[-1] ** -0.5, jnp.float32)
                qk = (jnp.arange(shape[0]) < n_qk)[:, None]
                out[name] = jnp.where(qk, w * np.float32(QK_GAIN),
                                      w).astype(dtype)
            else:
                fan_in = shape[-2] if "_experts_" in name else shape[-1]
                out[name] = normal(k, shape, fan_in ** -0.5)
        return out

    # hardware bit generator: billions of values by threefry take long
    key = jax.random.key(int(seed) % (2 ** 31), impl="unsafe_rbg")
    key = jax.random.fold_in(key, int(seed) >> 31)
    return jax.jit(make)(key)


def build(cfg, seed):
    """(description, params, engine) from the configuration file."""
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
    """The sizes ``arith_branch`` needs, under its own names."""
    d = reference_branch.dims(cfg)
    return {"d_model": d["D"], "vocab": d["V"], "kinds": d["kinds"],
            "num_heads": d["Hq"], "kv_heads": d["Hkv"], "head_dim": d["Dh"],
            "m_heads": d["H"], "m_head_dim": d["P"], "state": d["N"],
            "groups": d["G"], "conv": d["K"],
            "num_experts": d["E"], "top_k": d["k"], "held": d["held"],
            "expert_ff": d["F"], "latent": d["latent"],
            "shared_ff": d["Fs"],
            "block_size": cfg["engine"]["block_size"],
            "max_batch": cfg["engine"]["max_batch"]}


def sibling_dims(d):
    """What the accepted readers of the two sibling kinds read through
    ``ctx["hybrid"]`` (the state pool's slots and states, the attention
    layers' K/V) and ``ctx["moe"]`` (the held experts and the routed
    layers), so that ``state.slot_util_mean``,
    ``kernel.ssm_update_roofline``, ``kernel.paged_packed_roofline``,
    ``moe.experts_hit_share`` and ``moe.load_max_over_mean`` hold here as
    they stand."""
    kinds = d["kinds"]
    return ({"max_batch": d["max_batch"], "n_mamba": kinds.count("mamba"),
             "m_heads": d["m_heads"], "m_head_dim": d["m_head_dim"],
             "state": d["state"], "n_attn": kinds.count("attention"),
             "kv_heads": d["kv_heads"], "head_dim": d["head_dim"]},
            {"held": d["held"], "num_experts": d["num_experts"],
             "top_k": d["top_k"], "layers": len(kinds),
             "dense": tuple(k != "moe" for k in kinds)})


def probed_block_error(cfg, params, probe, kind, fault=None):
    """The routed blocks of the window's last pass of ``kind`` ("decode" /
    "span") against the reference's on the same inputs: the relative
    distance of the outputs over every routed layer's probed rows
    (``err``), the worst layer's, and how many rows entered (padding rows
    are zero on both sides; rows under ``GAP_MIN`` are left out)."""
    import jax.numpy as jnp

    u_all, y_all = (np.asarray(a, np.float32) for a in probe[kind])
    num = den = 0.0
    worst, rows = 0.0, 0
    for at, layer in enumerate(probe["layers"]):
        u, y = u_all[:, at], y_all[:, at]
        _, gap = reference_branch.router(cfg, params, layer, jnp.asarray(u))
        keep = np.logical_and(np.asarray(gap) > GAP_MIN,
                              np.abs(u).sum(-1) > 0)
        if not keep.any():
            continue
        ref = np.asarray(reference_branch.ffn(cfg, params, layer,
                                              jnp.asarray(u), fault))[keep]
        diff = y[keep] - ref
        num, den = num + (diff * diff).sum(), den + (ref * ref).sum()
        worst = max(worst, _distance(y[keep], ref))
        rows += int(keep.sum())
    if not rows:
        return {"err": float("inf"), "worst_layer": float("inf"), "rows": 0}
    return {"err": float((num / den) ** 0.5), "worst_layer": worst,
            "rows": rows}


def input_drift(cfg, params, probe, newest):
    """The engine's inputs to its routed blocks against the reference's,
    at the newest position of the sampled running requests (``newest``:
    ``[(row of the decode probe, {layer: the reference's row})]``).
    ``u_err``: the relative distance at the first routed layer, in front
    of which nothing routes; ``by_layer``: at every routed layer;
    ``pick_flips``: ``[rows whose picks differ, rows]`` over all of them
    (``flips_by_layer``: the rows that differ at each); ``gap``: the
    median gap between a row's last pick and the next expert, in
    selection scores."""
    import jax.numpy as jnp

    u_all = np.asarray(probe["decode"][0], np.float32)
    by_layer, flips, gaps = [], [], []
    for at, layer in enumerate(probe["layers"]):
        mine = np.stack([u_all[row, at] for row, _ in newest])
        ref = np.stack([np.asarray(rows[layer]) for _, rows in newest])
        by_layer.append(_distance(mine, ref))
        picked, _ = reference_branch.router(cfg, params, layer,
                                            jnp.asarray(mine))
        want, gap = reference_branch.router(cfg, params, layer,
                                            jnp.asarray(ref))
        flips.append(int((np.sort(np.asarray(picked), -1)
                          != np.sort(np.asarray(want), -1)).any(-1).sum()))
        gaps += [float(g) for g in np.asarray(gap)]
    return {"u_err": by_layer[0], "by_layer": by_layer,
            "pick_flips": [sum(flips), len(newest) * len(probe["layers"])],
            "flips_by_layer": flips, "gap": float(np.median(gaps))}


def check(params, cfg, spec, finished, live, live_states, probe, fault=None):
    """Teacher-force the sampled requests' own tokens through the float32
    reference and compare (the module's docstring says what).  ``fault``:
    one of ``reference_branch.FAULTS``, for ``branch_controls.py``."""
    import jax
    import jax.numpy as jnp

    if len(finished) + len(live) < spec["finished"] + spec["live"] \
            or not live:
        return {"ok": False, "why": f"the window left {len(finished)} "
                f"finished and {len(live)} running requests to compare, "
                f"fewer than {spec['finished']} + {spec['live']}"}
    row_of = {rid: n for n, rid in enumerate(probe["decode_rids"])}
    regrets, spreads, newest = [], [], []
    dist2 = norm2 = f32_share = 0.0          # dist2, norm2: per state layer
    for rec, S in zip(finished + live, [None] * len(finished) + live_states):
        running = S is not None and rec.req.rid in row_of
        ref = reference_branch.teacher_force(
            cfg, params, rec.req.prompt, rec.req.tokens, fault=fault,
            pad_to=spec["max_len"], rows=spec["max_new"], taps=running)
        regrets += ref["regrets"]
        spreads.append(ref["logit_std"])
        if running:
            # the decode pass's row of this request is its newest position
            newest.append((row_of[rec.req.rid],
                           {i: rows[-1] for i, rows
                            in ref["ffn_inputs"].items()}))
        if S is None:
            continue
        dist2 += np.asarray(jnp.sum(jnp.square(S - ref["states"]),
                                    axis=(1, 2, 3)), np.float64)
        norm2 += np.asarray(jnp.sum(jnp.square(ref["states"]),
                                    axis=(1, 2, 3)), np.float64)
        low = jax.lax.bitcast_convert_type(S, jnp.uint32) & 0x1FFF
        f32_share += float(jnp.mean(low != 0)) / len(live)
    if not newest:
        return {"ok": False, "why": "none of the sampled running requests "
                "was in the window's last decode pass"}
    worst, mean = max(regrets), sum(regrets) / len(regrets)
    by_state_layer = [float(v) for v in
                      (dist2 / np.maximum(norm2, 1e-30)) ** 0.5]
    state_err = float((dist2.sum() / max(norm2.sum(), 1e-30)) ** 0.5)
    ffn = {kind: probed_block_error(cfg, params, probe, kind, fault)
           for kind in ("decode", "span")}
    ffn_err = max(v["err"] for v in ffn.values())
    drift = input_drift(cfg, params, probe, newest)
    return {"ok": bool(worst <= MAX_REGRET_TOL and mean <= MEAN_REGRET_TOL
                       and by_state_layer[0] <= STATE_ERR_FIRST_TOL
                       and f32_share >= STATE_F32_SHARE_MIN
                       and ffn_err <= FFN_ERR_TOL
                       and drift["u_err"] <= U_ERR_TOL),
            "max_regret": worst, "tol": MAX_REGRET_TOL,
            "mean_regret": mean, "mean_tol": MEAN_REGRET_TOL,
            "state_err": state_err,
            "state_err_first": by_state_layer[0],
            "state_err_first_tol": STATE_ERR_FIRST_TOL,
            "state_err_by_layer": by_state_layer,
            "state_f32_share": f32_share,
            "state_f32_share_min": STATE_F32_SHARE_MIN,
            "ffn_err": ffn_err, "ffn_err_tol": FFN_ERR_TOL,
            "ffn_err_by_pass": ffn,
            "u_err": drift["u_err"], "u_err_tol": U_ERR_TOL,
            "u_err_by_layer": drift["by_layer"],
            "pick_flips": drift["pick_flips"],
            "pick_flips_by_layer": drift["flips_by_layer"],
            "router_gap": drift["gap"],
            "finished": len(finished), "live": len(live),
            "tokens": len(regrets),
            "shapes": [[len(r.req.prompt), len(r.req.tokens)]
                       for r in finished + live],
            "reference_logit_std": sum(spreads) / len(spreads)}


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
                 span_attention=status["span_attention"],
                 state_cache=status["state_cache"],
                 kv_cache=status["kv_cache"], decoder=status["decoder"],
                 weight_bytes=int(sum(v.nbytes for v in params.values())))

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
    probe = eng.routed_probe()      # what the window's last passes computed
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
                 tpot_ms_p90=e2e["tpot_ms_p90"],
                 # who the tail is: [mean gap ms, tokens so far, finished]
                 tpot_tail=sorted(
                     ([round(r.tpot(out["end"]) * 1e3, 2), r.seen,
                       r.finish_t is not None] for r in recs
                      if not r.failed and r.tpot(out["end"]) is not None),
                     reverse=True)[:16],
                 out_tok_s=e2e["out_tok_s"])
    phases = None
    if marks["phases"] and phases_end:
        phases = {k: phases_end[k] - marks["phases"].get(k, 0.0)
                  for k in phases_end}
    d = dims(cfg)
    hybrid, moe = sibling_dims(d)
    ctx = {"kind": "branch", "branch": d, "hybrid": hybrid, "moe": moe,
           "steps": out["steps"], "late_ms": [r.late * 1e3 for r in recs],
           "phase_seconds": phases, "window": out, "stats": stats}
    eng.shutdown()                  # the pools' room goes to the reference
    tic = time.perf_counter()
    verdict = check(params, cfg, mix["check"], finished, live, live_states,
                    probe)
    cell["info"](check=verdict, check_s=time.perf_counter() - tic)
    # for branch_controls.py
    cell["sampled"] = (params, finished, live, live_states, probe)
    return verdict["ok"], len(recs), n_failed, e2e, ctx
