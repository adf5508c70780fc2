"""A serving cell of kind ``moe``: ``mx.serve.Engine`` over
``mx.models.moe_decoder`` (window and global attention layers in two
block groups, routed and shared experts, this chip's share of the experts
and of the vocabulary), driven exactly as a ``serve`` cell is.

Imported from ``serve_cell``, never copied: the driver (``drive``), how a
request's latencies are taken (``latencies_ms``) and which programs the
traffic hits (``programs_for``).  This file's own: the weights
(``make_params``, from ``--seed``), how the engine is built from the
configuration's source keys, ``dims`` for ``arith_moe``, and the
comparison that decides ``correct``.

**What ``correct`` compares.**  Nothing is served for the check alone:
when the window has closed, ``sample`` takes requests the WINDOW served
(some it finished, some still decoding in their slots; one whose context
passed ``long_context``, so that YaRN's blended band and a window group
whose blocks were freed many times over are in it, one under
``short_context``) and ``check`` teacher-forces each one's prompt and
tokens through ``reference_moe.py`` (float32, no code shared with
``mxnet_tpu``), given the same share of experts and vocabulary.  Four
numbers, each with its own limit, set between this configuration's own
readings on the chip and those of ``moe_controls.py``'s faulty references
(PERF.md, PR 34):

- ``max_regret`` / ``mean_regret``: at every generated position the
  reference's best logit minus its logit of the engine's token, over the
  sliced vocabulary.  A router is a discontinuity: bfloat16 activations
  against the float32 reference flip the last pick of about one row in
  ten a layer (``pick_flips`` counts them, below), and a flipped pick
  moves a row by a fraction of an expert's output, so these read tens of
  times a dense decoder's and tell a wrong MODEL (window, normalisation,
  rotary), not a precision.
- ``ffn_err``: the precision the routed blocks state (a float32 router,
  float32 accumulation in the experts), read off WHAT THE WINDOW RAN.
  Every serving program leaves the first ``max_batch`` rows of its routed
  blocks' inputs and outputs in the engine's probe
  (``Engine.routed_probe``): when the window has closed the probe holds
  the last decode pass's rows and the last prefill or chunk pass's, of
  every routed layer, as those compiled programs computed them.  The
  reference's routed block runs on the same inputs and ``ffn_err`` is the
  relative distance of the outputs, the larger of the two passes'.  On
  identical inputs the picks agree (rows whose last pick the reference
  decides by under ``GAP_MIN`` are left out: 2 %), and what is left is
  the rounding of the block's intermediates; a router or an accumulation
  in bfloat16 on either side reads several times that.
- ``u_err``: how far the engine's input to the FIRST routed block is from
  the reference's at the newest position of the sampled running requests
  (the same probe; the reference's row from the teacher-forced pass).
  In front of that block lie the embedding, a full and a window attention
  layer (48 and 72 heads, both rotary schemes, the per-head gates) and
  the dense layer, and no router: nothing there can flip, so this reads
  the activation dtype's rounding and tells a fault in any of them.

``pick_flips`` (no limit: the witness of the first bullet) counts, over
the same rows and every routed layer, the rows whose picks from the
engine's input differ from the picks from the reference's
(``pick_flips_by_layer``: a layer's input carries every flip in front of
it, so the count grows down the stack).
"""

import time

import numpy as np

import arith
import arith_moe
import reference_moe
import span_readers
import traffic as traffic_mod
from serve_cell import _phase_totals, drive, latencies_ms, programs_for

# The limits of ``correct``, each between two readings (my chip runs, PR 34;
# PERF.md section 4): as configured over the seeds | the nearest control.
MAX_REGRET_TOL = 3.5        # 0.92-1.88 over 14 runs | 6.44-7.57 (held_norm)
MEAN_REGRET_TOL = 0.065     # 0.0297-0.0402 | 0.1006-0.1153 (window_496)
FFN_ERR_TOL = 0.0066        # 0.00352-0.00356, 5 runs | 0.0102 (acc_bf16)
U_ERR_TOL = 0.025           # 0.0126-0.0148, 5 runs | 0.0323 (window_496)
# a probed row enters ffn_err only if the reference decides its last pick
# by more than this (router logits; the engine's float32 router differs
# from the reference's by 1e-6 on the same input)
GAP_MIN = 1e-3
# query and key rows are N(0, gain^2 / fan_in), so that the scores' spread is
# SCORE_SPREAD in both kinds of layer: see make_params
SCORE_SPREAD = 2.0


def describe(cfg):
    """The decoder's description from the configuration's source keys."""
    import mxnet_tpu as mx
    from mxnet_tpu.models.moe import Rope

    d = reference_moe.dims(cfg)
    rope = {}
    for kind, rp in cfg["rope_parameters"].items():
        if not isinstance(rp, dict):
            continue
        yarn = None
        if rp["rope_type"] == "yarn":
            yarn = (rp["factor"], rp["original_max_position_embeddings"],
                    rp["beta_fast"], rp["beta_slow"], rp["attention_factor"])
        rope[kind] = Rope(int(round(d["Dh"] * rp["partial_rotary_factor"])),
                          float(rp["rope_theta"]), yarn)
    return mx.models.moe_decoder(
        d["V"], d["D"], d["kinds"], d["heads"], d["Hkv"], d["Dh"],
        d["window"], ["dense" if dense else "moe" for dense in d["dense"]],
        d["F_dense"], d["E"], d["k"], d["F"], d["Fs"],
        routed_scale=d["scale"], experts_held=(d["offset"], d["held"]),
        rope=rope, eps=d["eps"], name=reference_moe.NAME)


def qk_gain(dec, kind):
    """What the query and key rows of a layer of ``kind`` are scaled by:
    with unit-variance inputs the scores ``q.k / sqrt(Dh)`` then have
    spread ``SCORE_SPREAD``.  The rotated dimensions' products carry the
    rotary scale squared (YaRN's attention factor)."""
    rope = dec.rope_of(kind)
    rot = rope.dim / dec.head_dim
    var = rot * rope.scale ** 4 + (1.0 - rot)
    return float((SCORE_SPREAD / var ** 0.5) ** 0.5)


def make_params(dec, dtype, seed):
    """Random parameters for the decoder's ``param_shapes()``, made on the
    device from the seed in ONE jitted call, in the dtype they are served
    in (the benchmark's own: an edit of the program's initialiser cannot
    move what the limits mean).  Matrices N(0, 1/fan_in) (an expert's fan
    in is its rows' width), norm gains 1; query and key rows times
    ``qk_gain`` of their layer's kind.  The router's rows stay N(0,
    1/fan_in): logits of spread 1.  The seed enters as data."""
    import jax
    import jax.numpy as jnp

    shapes = dec.param_shapes()
    dtype = jnp.dtype(dtype)

    def make(key):
        out = {}
        for i, (name, shape) in enumerate(shapes.items()):
            k = jax.random.fold_in(key, i)
            if name.endswith("gamma"):
                out[name] = jnp.ones(shape, dtype)
                continue
            fan_in = shape[-2] if "_experts_" in name else shape[-1]
            w = jax.random.normal(k, shape, jnp.float32) \
                * np.float32(fan_in ** -0.5)
            if name.endswith("qkv_weight"):
                layer = int(name.split("_l")[-1].split("_")[0])
                n_qk = (dec.heads[layer] + dec.kv_heads) * dec.head_dim
                gain = np.float32(qk_gain(dec, dec.layer_types[layer]))
                w = jnp.where((jnp.arange(shape[0]) < n_qk)[:, None],
                              w * gain, w)
            out[name] = w.astype(dtype)
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
    """The sizes ``arith_moe`` needs, under its own names."""
    d = reference_moe.dims(cfg)
    return {"d_model": d["D"], "vocab": d["V"], "layers": d["L"],
            "heads": d["heads"], "kv_heads": d["Hkv"], "head_dim": d["Dh"],
            "window": d["window"],
            "window_layer": tuple(k == "sliding_attention"
                                  for k in d["kinds"]),
            "dense": d["dense"], "d_ff": d["F_dense"],
            "num_experts": d["E"], "top_k": d["k"], "held": d["held"],
            "expert_ff": d["F"], "shared_ff": d["Fs"],
            "block_size": cfg["engine"]["block_size"],
            "max_batch": cfg["engine"]["max_batch"]}


def sample(out, spec, seed):
    """The requests of the window that ``check`` compares: (finished,
    live), lists of the driver's records.  ``finished`` ended inside the
    window; ``live`` were decoding in their slots when it closed, with at
    least ``min_tokens`` generated.  Over both kinds together: the one
    with the longest context and the one with the shortest, then others
    drawn by the seed, ``spec["finished"]`` and ``spec["live"]`` in all;
    where the window finished fewer (the tests' window is a second long),
    running ones make up the number."""
    def fits(r):
        return not r.failed and len(r.req.tokens) >= spec["min_tokens"]

    def context(r):
        return len(r.req.prompt) + len(r.req.tokens)

    rng = np.random.default_rng(int(seed) + 1)
    done = [r for r in out["all"] if r.finish_t is not None and fits(r)
            and out["start"] <= r.finish_t <= out["end"]]
    live = [r for r in out["all"] if r.finish_t is None and fits(r)
            and r.req.status == "running"]        # it holds a slot
    n_done = min(spec["finished"], len(done))
    n_live = min(spec["live"] + spec["finished"] - n_done, len(live))
    both = done + live
    if not both:
        return [], []
    picked = {id(r): r for r in (max(both, key=context),
                                 min(both, key=context))}

    def take(cands, k):
        have = [r for r in cands if id(r) in picked]
        rest = [r for r in sorted(cands, key=lambda r: r.due)
                if id(r) not in picked]
        for i in rng.permutation(len(rest))[:max(k - len(have), 0)]:
            have.append(rest[i])
        return sorted(have, key=lambda r: r.due)

    return take(done, n_done), take(live, n_live)


def check(params, cfg, spec, finished, live, probe, fault=None):
    """Teacher-force the sampled requests' own tokens through the float32
    reference and compare, and the engine's ``probe`` with the
    reference's routed blocks (the module's docstring says what).
    ``fault``: one of ``reference_moe.FAULTS``, for ``moe_controls.py``."""
    if len(finished) + len(live) < spec["finished"] + spec["live"]:
        return {"ok": False, "why": f"the window left {len(finished)} "
                f"finished and {len(live)} running requests to compare, "
                f"fewer than {spec['finished']} + {spec['live']}"}
    recs = finished + live
    contexts = [len(r.req.prompt) + len(r.req.tokens) for r in recs]
    if max(contexts) <= spec["long_context"] \
            or min(contexts) >= spec["short_context"]:
        return {"ok": False, "why": f"contexts {sorted(contexts)}: none past "
                f"{spec['long_context']} or none under "
                f"{spec['short_context']}"}
    d = reference_moe.dims(cfg)
    block = min(512, cfg["engine"]["max_model_len"] // 4)
    row_of = {rid: n for n, rid in enumerate(probe["decode_rids"])}
    regrets, spreads, per_request, newest = [], [], [], []
    for rec in recs:
        running = rec.finish_t is None and rec.req.rid in row_of
        ref = reference_moe.teacher_force(
            cfg, params, rec.req.prompt, rec.req.tokens, fault=fault,
            block=block, taps=running)
        regrets += ref["regrets"]
        spreads.append(ref["logit_std"])
        per_request.append([max(ref["regrets"]),
                            sum(ref["regrets"]) / len(ref["regrets"])])
        if running:
            # the decode pass's row of this request is its newest position
            newest.append((row_of[rec.req.rid],
                           {i: rows[-1] for i, rows
                            in ref["ffn_inputs"].items()}))
    if not newest:
        return {"ok": False, "why": "none of the sampled running requests "
                "was in the window's last decode pass"}
    worst, mean = max(regrets), sum(regrets) / len(regrets)
    ffn = {kind: probed_block_error(cfg, params, probe, kind, fault)
           for kind in ("decode", "span")}
    ffn_err = max(v["err"] for v in ffn.values())
    drift = input_drift(cfg, params, probe, newest)
    return {"ok": bool(worst <= MAX_REGRET_TOL and mean <= MEAN_REGRET_TOL
                       and ffn_err <= FFN_ERR_TOL
                       and drift["u_err"] <= U_ERR_TOL),
            "max_regret": worst, "tol": MAX_REGRET_TOL,
            "mean_regret": mean, "mean_tol": MEAN_REGRET_TOL,
            "ffn_err": ffn_err, "ffn_err_tol": FFN_ERR_TOL,
            "ffn_err_by_pass": ffn,
            "u_err": drift["u_err"], "u_err_tol": U_ERR_TOL,
            "u_err_by_layer": drift["by_layer"],
            "pick_flips": drift["pick_flips"],
            "pick_flips_by_layer": drift["flips_by_layer"],
            "router_gap": drift["gap"],
            "finished": len(finished), "live": len(live),
            "tokens": len(regrets),
            "shapes": [[len(r.req.prompt), len(r.req.tokens)] for r in recs],
            "per_request": per_request,
            "reference_logit_std": sum(spreads) / len(spreads)}


def _distance(a, b):
    """Relative distance of ``a`` from ``b``, both (rows, D)."""
    diff = a - b
    return float((diff * diff).sum() ** 0.5
                 / max(float((b * b).sum()) ** 0.5, 1e-30))


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
        _, gap = reference_moe.router(cfg, params, layer, jnp.asarray(u))
        keep = np.logical_and(np.asarray(gap) > GAP_MIN,
                              np.abs(u).sum(-1) > 0)
        if not keep.any():
            continue
        ref = np.asarray(reference_moe.ffn(cfg, params, layer,
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
    median gap between a row's last pick and the next
    expert, in router logits."""
    import jax.numpy as jnp

    u_all = np.asarray(probe["decode"][0], np.float32)
    by_layer, flips, gaps = [], [], []
    for at, layer in enumerate(probe["layers"]):
        mine = np.stack([u_all[row, at] for row, _ in newest])
        ref = np.stack([np.asarray(rows[layer]) for _, rows in newest])
        by_layer.append(_distance(mine, ref))
        picked, _ = reference_moe.router(cfg, params, layer,
                                         jnp.asarray(mine))
        want, gap = reference_moe.router(cfg, params, layer,
                                         jnp.asarray(ref))
        flips.append(int((np.sort(np.asarray(picked), -1)
                          != np.sort(np.asarray(want), -1)).any(-1).sum()))
        gaps += [float(g) for g in np.asarray(gap)]
    return {"u_err": by_layer[0], "by_layer": by_layer,
            "pick_flips": [sum(flips), len(newest) * len(probe["layers"])],
            "flips_by_layer": flips,
            "gap": float(np.median(gaps))}


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
    jax.block_until_ready(params)
    cell["info"](weights_and_engine_s=time.perf_counter() - tic,
                 paged_attention=status["paged_attention"],
                 kv_groups=status["kv_groups"],
                 kv_cache=status["kv_cache"],
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
    groups = eng.statusz()["kv_groups"]
    ran = {(e["kind"], int(e["bucket"])) for e in eng.manifest()}
    if compiled or not ran <= set(manifest):
        raise RuntimeError(
            f"{compiled} compilation(s) inside the window; programs run "
            f"but not warmed: {sorted(ran - set(manifest))}")
    finished, live = sample(out, mix["check"], cell["seed"])
    probe = eng.routed_probe()      # what the window's last passes computed

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
                 preemptions=stats.preemptions, kv_groups_at_end=groups,
                 ttft_ms_p50=arith.percentile(ttft, 50),
                 tpot_ms_p50=arith.percentile(tpot, 50),
                 out_tok_s=e2e["out_tok_s"])
    phases = None
    if marks["phases"] and phases_end:
        phases = {k: phases_end[k] - marks["phases"].get(k, 0.0)
                  for k in phases_end}
    ctx = {"kind": "moe", "moe": dims(cfg), "steps": out["steps"],
           "late_ms": [r.late * 1e3 for r in recs],
           "phase_seconds": phases, "window": out, "stats": stats}
    passes = span_readers.named(
        span_readers.in_window(ctx, "serve.prefill") or [], "serve.prefill")
    took = [s[span_readers.ARGS]["moe_short_layers"] for s in passes
            if "moe_short_layers" in s[span_readers.ARGS]]
    if took:        # a traced run: which path the spans' routed layers took
        cell["info"](span_passes=len(took), routed_layers_short=sum(took),
                     routed_layers=len(took) * arith_moe.n_moe(ctx["moe"]))
    eng.shutdown()                  # the pools' room goes to the reference
    tic = time.perf_counter()
    verdict = check(params, cfg, mix["check"], finished, live, probe)
    cell["info"](check=verdict, check_s=time.perf_counter() - tic)
    cell["sampled"] = (params, finished, live, probe)   # for moe_controls.py
    return verdict["ok"], len(recs), n_failed, e2e, ctx
