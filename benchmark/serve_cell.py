"""A serving cell: ``mx.serve.Engine`` over ``mx.models.gpt`` driven by one
thread of this process.

No HTTP front, no handler threads, no pool: the loop below submits every
request that is due, takes one ``Engine.step()``, stamps the clock once
and credits every request whose token list grew (a token is visible to a
client when its step returns), or sleeps until the next due instant.
How requests fall due is the mix's loop (``loops/<kind>.py``): on a fixed
schedule whatever the engine does (open), or the instant a client's last
request finished (closed).  Every latency is taken from the DUE instant.
"""

import gc
import time

import numpy as np

import arith
import reference
import traffic as traffic_mod


# -- the model and the engine --------------------------------------------------

def make_gpt_params(net, seq_len, dtype, seed):
    """Random parameters for a gpt() symbol, made on the device from the
    seed in ONE jitted call, in the dtype they are served in (after
    ``chip_smoke.make_gpt_params``, which makes them leaf by leaf).
    Matrices are N(0, 1/fan_in) so activations stay O(1) through the
    stack; norm gains are 1, biases 0.  The seed enters as data, so every
    seed runs the same compiled program."""
    import jax
    import jax.numpy as jnp

    arg_shapes, _, _ = net.infer_shape(data=(1, seq_len),
                                       softmax_label=(1, seq_len))
    shapes = {n: s for n, s in zip(net.list_arguments(), arg_shapes)
              if n not in ("data", "softmax_label")}

    def make(key):
        out = {}
        for i, (name, shape) in enumerate(shapes.items()):
            if name.endswith("gamma"):
                out[name] = jnp.ones(shape, dtype)
            elif name.endswith("weight"):
                w = jax.random.normal(jax.random.fold_in(key, i), shape,
                                      jnp.float32)
                out[name] = (w * shape[-1] ** -0.5).astype(dtype)
            else:
                out[name] = jnp.zeros(shape, dtype)
        return out

    # hardware bit generator: 3.8 G values by threefry take many seconds
    key = jax.random.key(int(seed) % (2 ** 31), impl="unsafe_rbg")
    key = jax.random.fold_in(key, int(seed) >> 31)
    return jax.jit(make)(key)


def build(cfg, seed):
    """(net, params, engine) as ``chip_smoke.serve_phase`` builds them,
    from the configuration file's published keys and engine geometry."""
    import mxnet_tpu as mx

    geo = cfg["engine"]
    net = mx.models.gpt(
        cfg["vocab_size"], geo["max_model_len"],
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"], d_ff=cfg["intermediate_size"],
        norm="rmsnorm", mlp="swiglu", pos_embed="rope",
        tie_embeddings=cfg["tie_word_embeddings"],
        kv_heads=cfg["num_key_value_heads"])
    params = make_gpt_params(net, geo["max_model_len"], cfg["dtype"], seed)
    eng = mx.serve.Engine(
        params, symbol=net, block_size=geo["block_size"],
        num_blocks=geo["num_blocks"], max_batch=geo["max_batch"],
        max_queue=geo["max_queue"], max_model_len=geo["max_model_len"],
        prefill_chunk=geo["prefill_chunk"], tp=geo["tp"])
    return net, params, eng


def model_dims(cfg):
    """The sizes ``arith`` needs, under its own names."""
    return {"num_layers": cfg["num_hidden_layers"],
            "d_model": cfg["hidden_size"],
            "num_heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"],
            "d_ff": cfg["intermediate_size"], "vocab": cfg["vocab_size"]}


# -- which programs the traffic hits ------------------------------------------

def next_bucket(n, cap):
    """Smallest power of two >= n, clamped to cap (the engine's rule)."""
    b = 1
    while b < n:
        b *= 2
    return min(b, cap)


def programs_for(prompt_lens, geo):
    """The (kind, bucket) programs requests of these prompt lengths can
    run, and no others: every decode bucket up to ``max_batch``; one
    whole-prompt prefill per prompt at or under ``prefill_chunk``; for a
    longer prompt every chunk it can be cut into (a chunk's budget is
    ``prefill_chunk`` less the decode slots running beside it, 0 to
    ``max_batch`` - 1)."""
    L, chunk, mb = geo["max_model_len"], geo["prefill_chunk"], geo["max_batch"]
    cap = next_bucket(chunk, L) if chunk > 0 else L
    progs, b = set(), 1
    while b < mb:
        progs.add(("decode", b))
        b *= 2
    progs.add(("decode", mb))
    for n in sorted(set(int(p) for p in prompt_lens)):
        if chunk <= 0 or n <= chunk:
            progs.add(("prefill", next_bucket(n, L)))
            continue
        left = {n}
        while left:
            nxt = set()
            for r in left:
                for d in range(mb):
                    span = min(r, max(1, chunk - d))
                    progs.add(("chunk", next_bucket(span, cap)))
                    if r - span > 0:
                        nxt.add(r - span)
            left = nxt
    return sorted(progs)


# -- correctness, outside the window -------------------------------------------

# The engine's greedy token may differ from the reference's argmax only by a
# near-tie.  Logits here have unit spread (N(0, 1/fan_in) weights under a
# final RMSNorm) and the largest of 32768 sits near +4.  bf16 weights, cache
# and activations against the float32 reference left, over 18 seeds of about
# 100 generated positions each, a worst regret of 0.006-0.043 and a mean
# regret of 0.00005-0.0011 (my chip runs, PR 24); both limits are about
# three times the worst seen.  They are meant to refuse a cache or a matmul
# below bf16 (int8 or fp8 keys and values, fp8 weights: 2-3 fewer mantissa
# bits) that a PR does not declare: the worst regret grows with the error,
# the mean with its square, so the mean is the sharper of the two.  A wrong
# block, head, mask or position gives the chosen token an unrelated
# reference logit, a regret of about 4.
REGRET_TOL = 0.12          # worst position
REGRET_MEAN_TOL = 0.0035   # mean over all generated positions


def check(eng, params, cfg, spec, seed):
    """Serve the mix's check sequences alone, then teacher-force the
    engine's own tokens through the float32 reference: at every generated
    position reference-logit(engine's token) >= reference max - tol, and
    the mean shortfall stays under its own, tighter limit."""
    rng = np.random.default_rng(int(seed) + 1)
    reqs = [eng.submit(traffic_mod.token_ids(rng, p, cfg["vocab_size"]),
                       max_new_tokens=int(g))
            for p, g in spec["sequences"]]
    eng.run()
    regrets = []
    for req, (p, g) in zip(reqs, spec["sequences"]):
        if len(req.tokens) != g:
            return {"ok": False, "why": f"{len(req.tokens)} of {g} tokens"}
        regrets += reference.greedy_regret(
            params, req.prompt, req.tokens, cfg["num_attention_heads"],
            cfg["num_key_value_heads"])
    worst, mean = max(regrets), sum(regrets) / len(regrets)
    return {"ok": bool(worst <= REGRET_TOL and mean <= REGRET_MEAN_TOL),
            "max_regret": worst, "tol": REGRET_TOL, "mean_regret": mean,
            "mean_tol": REGRET_MEAN_TOL, "sequences": len(reqs),
            "tokens": len(regrets)}


# -- the driver ----------------------------------------------------------------

class Rec:
    """The driver's own stamps for one request."""
    __slots__ = ("req", "due", "late", "first_t", "last_t", "seen",
                 "cache_seen", "finish_t", "failed")

    def __init__(self, req, due, now):
        self.req, self.due, self.late = req, due, now - due
        self.first_t = self.last_t = self.finish_t = None
        self.seen = self.cache_seen = 0
        self.failed = False

    def ttft(self, end):
        """First token visible - due.  With no token by the window's end
        the request has waited ``end - due`` already: it enters as that."""
        return (end if self.first_t is None else self.first_t) - self.due

    def tpot(self, end):
        """Mean gap between this request's output tokens, (last - first) /
        (tokens - 1).  For a request still running at the window's end
        these are the gaps it has so far, and the gap then open counts as
        one more, closed at the end, where that makes the mean longer (a
        stall shows).  None before the first token, and for a finished
        request of one token."""
        if self.first_t is None:
            return None
        gaps = self.seen - 1
        mean = (self.last_t - self.first_t) / gaps if gaps else None
        if self.finish_t is None:
            return max(mean or 0.0, (end - self.first_t) / self.seen)
        return mean


def drive(eng, loop, seconds, tracer=None, clock=time.perf_counter,
          sleep=time.sleep, on_window=None):
    """Run the loop against the engine; returns the window's records.

    ``loop`` (``loops/<kind>.py``) says how requests fall due:
    ``start(t0)``; ``take(now)`` -> [(token ids, new tokens, due)] to
    submit now; ``on_finish(rec, now)``; ``next_due()`` -> when to wake an
    idle engine (None: an idle engine is an error);
    ``window_opens(now, finished)``.  The window opens at the first
    instant between two steps at which the loop says so, lasts ``seconds``
    and closes at the end of the step then in flight; the run ends with
    it (no drain).  The measured requests are ALL those due inside the
    window, finished or not (``Rec.ttft``/``tpot`` say how an unfinished
    one enters).  ``on_window()`` is called as the window opens and must
    return quickly."""
    import jax
    from mxnet_tpu.serve import QueueFull, REJECTED

    live, gone, steps = [], [], []
    finished = 0
    win = {"start": None, "end": None, "tokens": 0}
    ann = jax.profiler.TraceAnnotation

    gc.collect()
    t0 = clock()
    loop.start(t0)
    while True:
        now = clock()
        if win["start"] is None and loop.window_opens(now, finished):
            if on_window is not None:
                on_window()
            now = clock()
            win["start"], win["end"] = now, now + seconds
        if win["start"] is not None:
            if now >= win["end"]:
                win["end"] = now
                break
            if tracer is not None:
                tracer.maybe_start(now, win["end"])
        with ann("bench.submit"):
            for ids, new_tokens, due in loop.take(now):
                try:
                    req = eng.submit(ids, max_new_tokens=new_tokens)
                except QueueFull:
                    req = None
                rec = Rec(req, due, clock())
                if req is None or req.status == REJECTED:
                    rec.failed, rec.finish_t = True, rec.due + rec.late
                    gone.append(rec)
                else:
                    live.append(rec)
        if not eng.has_work():
            nxt = loop.next_due()
            if nxt is None and win["end"] is None:
                raise RuntimeError("the loop ran out of work")
            if win["end"] is not None:
                nxt = win["end"] if nxt is None else min(nxt, win["end"])
            sleep(max(0.0, nxt - clock()))
            continue
        eng.step()
        t = clock()
        with ann("bench.credit"):
            decoded = ctx = 0
            passes = []
            for rec in live:
                req = rec.req
                n_tok = len(req.tokens)
                grew = n_tok - rec.seen
                if rec.seen == 0 and req.cache_len > rec.cache_seen:
                    # a prefill pass (whole prompt or one chunk); the last
                    # one also yields the first token
                    passes.append((req.cache_len - rec.cache_seen,
                                   req.cache_len))
                    if grew:
                        rec.first_t = t
                elif grew:
                    decoded += 1
                    ctx += req.cache_len
                if grew:
                    if win["start"] is not None:
                        win["tokens"] += grew
                    rec.seen, rec.last_t = n_tok, t
                rec.cache_seen = req.cache_len
                if req.done:
                    rec.finish_t = t
                    rec.failed = (req.status == REJECTED
                                  or n_tok < req.max_new_tokens)
            done = [r for r in live if r.finish_t is not None]
            if done:
                live[:] = [r for r in live if r.finish_t is None]
                gone += done
                finished += sum(not r.failed for r in done)
                for rec in done:
                    loop.on_finish(rec, t)
            if win["start"] is not None:
                steps.append((t, decoded, ctx, passes,
                              eng.blocks.utilization(),
                              _engine_decode_batch(eng),
                              eng.scheduler.queue_depth))
    # due inside the window and never submitted: the last step was in flight
    unsent = [Rec(None, due, win["end"])
              for _, _, due in loop.take(win["end"])]
    due_in = [r for r in gone + live + unsent
              if win["start"] <= r.due <= win["end"]]
    return {"t0": t0, "start": win["start"], "end": win["end"],
            "window_s": win["end"] - win["start"], "tokens": win["tokens"],
            "steps": steps, "records": due_in, "all": gone + live,
            "finished_in_window": sum(
                r.finish_t is not None and not r.failed
                and win["start"] <= r.finish_t <= win["end"] for r in gone)}


def latencies_ms(out):
    """(TTFTs, TPOTs) in ms over every request due inside the window.  A
    failed request enters both as infinite: it misses any limit."""
    end, ttft, tpot = out["end"], [], []
    for r in out["records"]:
        if r.failed:
            ttft.append(float("inf"))
            tpot.append(float("inf"))
            continue
        ttft.append(r.ttft(end) * 1e3)
        gap = r.tpot(end)
        if gap is not None:
            tpot.append(gap * 1e3)
    return ttft, tpot


def _engine_decode_batch(eng):
    """The decode batch of the step just taken, as the engine's own
    ``StatsRecorder.on_step`` recorded it (None if that moved)."""
    try:
        return eng._stats._window[-1][2]
    except (AttributeError, IndexError, TypeError):
        return None


def _phase_totals(eng):
    """Cumulative ``StepProfiler`` seconds per phase (None when off)."""
    prof = eng.statusz().get("step_profile") or {}
    return dict(prof["totals_s"]) if prof.get("totals_s") else None


# -- one run ----------------------------------------------------------------------

def run(cell):
    """``cell``: the run's context from ``run.py`` (config, traffic mix,
    seed, seconds, tracer, compile counter, info()).  Returns
    (correct, attempted, failed, end_to_end, context for the readers)."""
    import jax

    cfg, mix = cell["config"], cell["traffic"]
    on_tpu = jax.devices()[0].platform == "tpu"
    tic = time.perf_counter()
    net, params, eng = cell["build"](cfg, cell["seed"])
    paged = eng.statusz()["paged_attention"]
    if on_tpu and paged != "pallas":
        raise RuntimeError(f"decode attention resolved to {paged!r} on a "
                           "TPU: the cell must run the Pallas paged kernel")
    jax.block_until_ready(params)
    cell["info"](weights_and_engine_s=time.perf_counter() - tic)

    loop = traffic_mod.loop(mix, cell["seed"], cell["seconds"],
                            cfg["vocab_size"])
    geo = cfg["engine"]
    lens = list(loop.prompt_len) + [p for p, _ in mix["check"]["sequences"]]
    manifest = programs_for(lens, geo)
    tic = time.perf_counter()
    ready = eng.warmup([{"kind": k, "bucket": b} for k, b in manifest])
    if ready != len(manifest):
        raise RuntimeError(f"warmed {ready} of {len(manifest)} programs")
    cell["info"](programs=len(manifest), warmup_s=time.perf_counter() - tic)

    tic = time.perf_counter()
    verdict = check(eng, params, cfg, mix["check"], cell["seed"])
    cell["info"](check=verdict, check_s=time.perf_counter() - tic)

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
                 tpot_ms_p50=arith.percentile(tpot, 50))
    phases = None
    if marks["phases"] and phases_end:
        phases = {k: phases_end[k] - marks["phases"].get(k, 0.0)
                  for k in phases_end}
    ctx = {"kind": "serve", "model": model_dims(cfg), "steps": out["steps"],
           "late_ms": [r.late * 1e3 for r in recs],
           "phase_seconds": phases, "window": out, "stats": stats}
    eng.shutdown()
    return verdict["ok"], len(recs), n_failed, e2e, ctx
