"""A serving cell of kind ``diffusion``: ``mx.serve.Engine`` over
``mx.models.moe_decoder`` described as a block-diffusion decoder (SDAR:
Qwen3-MoE layers under a block-causal mask, every layer attention and 128
softmax-routed experts; a decode pass denoises every row's block of 4 and
tokens are committed a block at a time), driven exactly as a ``serve``
cell is.

Imported, never copied: the traffic's driver (``serve_cell.drive``), the
latencies and the programs the traffic hits (``serve_cell``) and how the window's requests are sampled
(``hybrid_cell.sample``).  This
file's own: the weights (``make_params``, from ``--seed``), how the engine
is built from the configuration's source keys, ``dims`` for
``arith_diffusion``, and the comparison that decides ``correct``.

**What ``correct`` compares.**  Nothing is served for the check alone: when
the window has closed, requests the WINDOW served (some it finished, some
still denoising in their slots) are replayed through
``reference_diffusion.py`` (float32, no code shared with ``mxnet_tpu``),
teacher-forced by the engine's own unmask record (``Request.unmasked``:
every position a denoise pass unmasked, its token and its step).  Held,
each to a limit of its own set between this configuration's readings on
the chip and those of ``diffusion_controls.py``'s faulty references
(PERF.md, section 4):

- ``mean_regret``: at every position the engine unmasked, the
  reference's best logit (mask id excluded) minus its logit of the
  engine's token, the block as the engine had it before that step; the
  largest, ``max_regret``, is reported and not held.
- ``order_regret``: at every step, the reference's confidence of the
  position it would unmask (the n-th most confident masked one) minus its
  lowest at the engine's choices, 0 where the engine took the reference's
  order; the MEAN over the steps is held (a router flip in front of the
  head may reorder one step's close confidences; a wrong order rule
  reorders most steps), the largest reported.
- ``u_err``: the engine's input to the first routed block (layer 0's,
  behind the block-causal attention, QK norms and rotary, in front of any
  router) against the reference's, the larger of two readings: the
  window's last decode pass, for the sampled running rows in it (the same
  block and context), and the window's last span pass, the probe's rows
  of its prompt from the span's start (a prompt's first positions see a
  few keys, so there the block mask, not the context, decides them).
- ``ffn_err``: the routed blocks' outputs of the window's last denoise
  pass and last span pass (the engine's probe) against the reference's
  block on the same inputs (rows whose last pick the reference decides by
  under ``GAP_MIN`` left out).
- ``kv_err``: layer 0's K and V in the engine's cache over the last
  committed block of each sampled running request against the reference's
  clean block: what the store pass wrote.

Besides, the record itself must hold: every committed block unmasked its
positions in the static schedule's counts (``schedule_ok``), and the
tokens the request got are the committed blocks' in order
(``tokens_match``).
"""

import time

import numpy as np

import arith
import reference_diffusion
import traffic as traffic_mod
from hybrid_cell import sample
from serve_cell import _phase_totals, drive, latencies_ms, programs_for

# The limits of ``correct``, each between two readings on one TPU v5e chip
# (PERF.md section 4): the largest as configured over the seeds | the
# lowest of the controls (``diffusion_controls.py``, 4 seeds each) that
# read at least three times that largest one, and so tell a fault from
# the sound spread.  ``max_regret`` is reported, not held: no control
# reads three times its largest sound reading (5.04) on every seed, and
# each is told by another number.
MEAN_REGRET_TOL = 0.3       # 0.147 | 0.551 (causal_in_block)
ORDER_REGRET_TOL = 0.03     # 0.0146 | 0.048 (stale_kv)
U_ERR_TOL = 0.03            # 0.0071 | 0.186 (causal_in_block)
# the precision controls: router_bf16 reads 0.044 at least; acc_bf16's
# 0.0089 is only 2.3 times the sound reading, 1.48 times this limit
FFN_ERR_TOL = 0.006         # 0.00389 | 0.044 (router_bf16)
KV_ERR_TOL = 0.03           # 0.0028 | 0.281 (no_qk_norm)
# a probed row enters ffn_err only if the reference decides its last pick
# by more than this (router logits)
GAP_MIN = 1e-3
# query and key heads are RMS-normed; their gains are QK_GAIN each, so that
# scores q.k / sqrt(head_dim) have spread 2
QK_GAIN = 2.0 ** 0.5
# the head's rows are N(0, HEAD_GAIN^2 / d_model): logits of spread 4, so
# that the order of a block's confidences is decided by more than bfloat16
# rounding (PERF.md section 4)
HEAD_GAIN = 4.0


def describe(cfg):
    """The decoder's description from the configuration's source keys."""
    import mxnet_tpu as mx
    from mxnet_tpu.models.moe import FULL, Rope

    d = reference_diffusion.dims(cfg)
    L = d["L"]
    return mx.models.moe_decoder(
        d["V"], d["D"], [FULL] * L, [d["H"]] * L, d["Hkv"], d["Dh"], 0,
        ["moe"] * L, cfg["intermediate_size"], d["E"], d["k"], d["F"], 0,
        rope={FULL: Rope(d["Dh"], d["theta"], None)}, eps=d["eps"],
        name=reference_diffusion.NAME, gate=False, qk_norm=True,
        diffusion=(d["B"], d["T"], d["mask"]))


def make_params(dec, dtype, seed):
    """Random parameters for the decoder's ``param_shapes()``, made on the
    device from the seed in ONE jitted call, in the dtype they are served
    in (the benchmark's own: an edit of the program's initialiser cannot
    move what the limits mean).  Matrices N(0, 1/fan_in) (an expert's fan
    in is its rows' width), norm gains 1 but the QK norms' (``QK_GAIN``),
    the head times ``HEAD_GAIN``.  The seed enters as data."""
    import jax
    import jax.numpy as jnp

    shapes = dec.param_shapes()
    dtype = jnp.dtype(dtype)

    def make(key):
        out = {}
        for i, (name, shape) in enumerate(shapes.items()):
            k = jax.random.fold_in(key, i)
            if name.endswith(("_q_norm_gamma", "_k_norm_gamma")):
                out[name] = jnp.full(shape, QK_GAIN, dtype)
            elif name.endswith("gamma"):
                out[name] = jnp.ones(shape, dtype)
            else:
                fan_in = shape[-2] if "_experts_" in name else shape[-1]
                gain = HEAD_GAIN if name.endswith("_head_weight") else 1.0
                out[name] = (jax.random.normal(k, shape, jnp.float32)
                             * np.float32(gain * fan_in ** -0.5)
                             ).astype(dtype)
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
    """The sizes ``arith_diffusion`` needs, under its own names."""
    d = reference_diffusion.dims(cfg)
    return {"d_model": d["D"], "vocab": d["V"], "layers": d["L"],
            "num_heads": d["H"], "kv_heads": d["Hkv"], "head_dim": d["Dh"],
            "num_experts": d["E"], "top_k": d["k"], "expert_ff": d["F"],
            "block": d["B"], "steps": d["T"],
            "block_size": cfg["engine"]["block_size"],
            "max_batch": cfg["engine"]["max_batch"]}


def sibling_dims(d):
    """What the accepted readers of routed experts read through
    ``ctx["moe"]`` (``arith_moe``'s names: all experts held, every layer
    routed, SwiGLU experts), so that ``moe.experts_hit_share``,
    ``moe.load_max_over_mean`` and ``kernel.moe_experts_roofline`` hold
    here as they stand."""
    return {"d_model": d["d_model"], "expert_ff": d["expert_ff"],
            "held": d["num_experts"], "num_experts": d["num_experts"],
            "top_k": d["top_k"], "layers": d["layers"],
            "dense": (False,) * d["layers"]}


def programs(prompt_lens, geo, block):
    """The (kind, bucket) programs the traffic can hit, by
    ``serve_cell.programs_for``: a prefill covers a prompt's whole blocks,
    and a chunk's budget is ``prefill_chunk`` less a block for each of
    the 0 to ``max_batch`` - 1 rows decoding beside it, so the chunks are
    enumerated over that many budgets (a superset: every budget a row
    apart) and the decode buckets over the engine's own batch."""
    whole = [int(n) - int(n) % block for n in prompt_lens]
    wide = dict(geo, max_batch=(geo["max_batch"] - 1) * block + 1)
    spans = {p for p in programs_for(whole, wide) if p[0] != "decode"}
    return sorted(spans | set(programs_for([], geo)))


def snapshot(eng, recs):
    """What the check needs of each sampled request, read before the
    engine is shut down: its prompt, tokens, committed length, unmask
    record and, for one still holding its blocks, layer 0's K and V of its
    last committed block ((2, B, Hkv, Dh), float32)."""
    B = eng._block
    out = []
    for rec in recs:
        req = rec.req
        kv = None
        if req.status == "running" and req.cache_len >= B:
            table = eng.blocks.table(req.rid)
            pos = np.arange(req.cache_len - B, req.cache_len)
            blk = np.asarray(table)[pos // eng.block_size]
            off = pos % eng.block_size
            kv = np.stack([np.asarray(c[0, blk, off], np.float32)
                           for c in (eng._cache_k, eng._cache_v)])
        out.append({"rid": req.rid, "prompt": np.asarray(req.prompt),
                    "tokens": list(req.tokens), "done": req.cache_len,
                    "unmasked": list(req.unmasked), "kv": kv,
                    "live": rec.finish_t is None})
    return out


def span_prompt(out, probe):
    """``(prompt, start)`` of the request whose span pass the probe holds
    last, from ``serve_cell.drive``'s records (None: no span was
    probed)."""
    if probe is None or probe["span_rows"] is None:
        return None
    rid, start = probe["span_rows"]
    for rec in out["all"]:
        if rec.req is not None and rec.req.rid == rid:
            return np.asarray(rec.req.prompt), int(start)
    return None


def probed_block_error(cfg, params, probe, kind, fault=None):
    """The routed blocks of the window's last pass of ``kind`` ("decode" /
    "span") against the reference's on the same inputs: the relative
    distance of the outputs over every routed layer's probed rows, and how
    many rows entered (padding rows are zero on both sides; rows under
    ``GAP_MIN`` are left out)."""
    import jax.numpy as jnp

    u_all, y_all = (np.asarray(a, np.float32) for a in probe[kind])
    num = den = 0.0
    rows = 0
    for at, layer in enumerate(probe["layers"]):
        u, y = u_all[:, at], y_all[:, at]
        _, gap = reference_diffusion.router(cfg, params, layer,
                                            jnp.asarray(u))
        keep = np.logical_and(np.asarray(gap) > GAP_MIN,
                              np.abs(u).sum(-1) > 0)
        if not keep.any():
            continue
        ref = np.asarray(reference_diffusion.ffn(
            cfg, params, layer, jnp.asarray(u[keep]), fault))
        diff = y[keep] - ref
        num, den = num + (diff * diff).sum(), den + (ref * ref).sum()
        rows += int(keep.sum())
    if not rows:
        return {"err": float("inf"), "rows": 0}
    return {"err": float((num / den) ** 0.5), "rows": rows}


def span_input_error(cfg, params, probe, span, fault=None):
    """The relative distance of the probe's first-routed-block inputs of
    the last span pass from the reference's over the same prompt
    positions (the probe's padding rows left out), or None."""
    if span is None:
        return None
    prompt, start = span
    want = reference_diffusion.prompt_inputs(cfg, params, prompt, fault)
    rows = np.asarray(probe["span"][0][:, 0], np.float32)
    n = min(len(rows), len(want) - start)
    # a chunk shorter than the probe leaves its padding rows zero
    real = np.abs(rows[:max(n, 0)]).sum(-1) > 0
    if n <= 0 or not real.any():
        return None
    mine, ref = rows[:n][real], want[start:start + n][real]
    diff = mine - ref
    return float(((diff * diff).sum() / max(float((ref * ref).sum()),
                                            1e-30)) ** 0.5)


def check(params, cfg, spec, seen, probe, span=None, fault=None):
    """Replay the sampled requests' blocks through the float32 reference,
    teacher-forced by their unmask records, and compare (the module's
    docstring says what).  ``seen``: :func:`snapshot`'s records; ``span``:
    :func:`span_prompt`'s; ``fault``: one of
    ``reference_diffusion.FAULTS``."""
    live = [s for s in seen if s["live"]]
    if len(seen) < spec["finished"] + spec["live"] or not live:
        return {"ok": False, "why": f"the window left {len(seen)} requests "
                f"({len(live)} running) to compare, fewer than "
                f"{spec['finished']} + {spec['live']}"}
    d = reference_diffusion.dims(cfg)
    B = d["B"]
    rows_of = {rid: (r, start, blk) for r, (rid, start, blk)
               in enumerate(probe["decode_blocks"])}
    regrets, order, spreads = [], [], []
    u_num = u_den = kv_num = kv_den = 0.0
    schedule_ok = tokens_match = True
    probed = kv_rows = 0
    for s in seen:
        committed = [u for u in s["unmasked"] if u[0] < s["done"]]
        current = [u for u in s["unmasked"] if u[0] >= s["done"]]
        at = rows_of.get(s["rid"]) if s["live"] else None
        ref = reference_diffusion.replay(
            cfg, params, s["prompt"], committed, current,
            probe=None if at is None else (at[1], at[2]), fault=fault)
        regrets += ref["regrets"]
        order += ref["order_regrets"]
        spreads.append(ref["logit_std"])
        schedule_ok &= ref["schedule_ok"]
        tokens_match &= ref["sequence"][:len(s["tokens"])] == s["tokens"]
        if at is not None:
            mine = np.asarray(probe["decode"][0][at[0] * B:(at[0] + 1) * B,
                                                 0], np.float32)
            diff = mine - ref["u0"]
            u_num += float((diff * diff).sum())
            u_den += float((ref["u0"] ** 2).sum())
            probed += 1
        if s["kv"] is not None and ref["kv0"] is not None:
            diff = s["kv"] - ref["kv0"]
            kv_num += float((diff * diff).sum())
            kv_den += float((ref["kv0"] ** 2).sum())
            kv_rows += 1
    if not probed or not kv_rows or not regrets:
        return {"ok": False, "why": f"{probed} sampled running requests in "
                f"the last decode pass, {kv_rows} with a committed block, "
                f"{len(regrets)} unmasked positions"}
    ffn = {kind: probed_block_error(cfg, params, probe, kind, fault)
           for kind in ("decode", "span")}
    ffn_err = max(v["err"] for v in ffn.values())
    worst, mean = max(regrets), sum(regrets) / len(regrets)
    order_worst, order_mean = max(order), sum(order) / len(order)
    u_decode = (u_num / max(u_den, 1e-30)) ** 0.5
    u_span = span_input_error(cfg, params, probe, span, fault)
    u_err = max(u_decode, u_span or 0.0)
    kv_err = (kv_num / max(kv_den, 1e-30)) ** 0.5
    return {"ok": bool(mean <= MEAN_REGRET_TOL
                       and order_mean <= ORDER_REGRET_TOL
                       and u_err <= U_ERR_TOL and ffn_err <= FFN_ERR_TOL
                       and kv_err <= KV_ERR_TOL and schedule_ok
                       and tokens_match),
            "max_regret": worst,
            "mean_regret": mean, "mean_tol": MEAN_REGRET_TOL,
            "order_regret": order_mean, "order_tol": ORDER_REGRET_TOL,
            "order_regret_max": order_worst,
            "u_err": u_err, "u_err_tol": U_ERR_TOL,
            "u_err_by_pass": {"decode": u_decode, "span": u_span},
            "ffn_err": ffn_err, "ffn_err_tol": FFN_ERR_TOL,
            "ffn_err_by_pass": ffn,
            "kv_err": kv_err, "kv_err_tol": KV_ERR_TOL,
            "schedule_ok": bool(schedule_ok),
            "tokens_match": bool(tokens_match),
            "requests": len(seen), "live": len(live), "probed": probed,
            "steps": len(order), "tokens": len(regrets),
            "shapes": [[len(s["prompt"]), len(s["tokens"])] for s in seen],
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
    jax.block_until_ready(params)
    cell["info"](weights_and_engine_s=time.perf_counter() - tic,
                 paged_attention=status["paged_attention"],
                 span_attention=status["span_attention"],
                 kv_cache=status["kv_cache"], decoder=status["decoder"],
                 weight_bytes=int(sum(v.nbytes for v in params.values())))

    # prompts from the text ids only: none holds the mask id
    loop = traffic_mod.loop(mix, cell["seed"], cell["seconds"],
                            cfg["text_ids"])
    geo = cfg["engine"]
    manifest = programs(loop.prompt_len, geo, cfg["block_length"])
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
    seen = snapshot(eng, finished + live)
    span = span_prompt(out, probe)

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
                 out_tok_s=e2e["out_tok_s"])
    phases = None
    if marks["phases"] and phases_end:
        phases = {k: phases_end[k] - marks["phases"].get(k, 0.0)
                  for k in phases_end}
    d = dims(cfg)
    ctx = {"kind": "diffusion", "diffusion": d, "moe": sibling_dims(d),
           "steps": out["steps"], "late_ms": [r.late * 1e3 for r in recs],
           "phase_seconds": phases, "window": out, "stats": stats}
    eng.shutdown()                  # the pools' room goes to the reference
    tic = time.perf_counter()
    verdict = check(params, cfg, mix["check"], seen, probe, span)
    cell["info"](check=verdict, check_s=time.perf_counter() - tic)
    cell["sampled"] = (params, seen, probe, span)  # for diffusion_controls
    return verdict["ok"], len(recs), n_failed, e2e, ctx
