"""Draft-model speculative decoding for the serving engine.

Plain continuous-batching decode emits exactly ONE token per running
request per iteration — decode throughput is bound by one bucketed
dispatch per token.  Speculative decoding (Leviathan et al. 2023;
Chen et al. 2023) breaks that bound: a small *draft* model proposes
``k`` tokens per request, and the *target* model scores all ``k+1``
positions in ONE bucketed verify dispatch.  With greedy (temperature
0) acceptance — keep the longest prefix of drafted tokens whose target
argmax agrees, plus the target's own token at the first disagreement —
the emitted stream is **provably token-identical to plain decode**: the
target's argmax decides every emitted token, the draft only decides how
many arrive per dispatch.

Per engine iteration with ``spec_k = k`` the decode batch costs

  1 draft dispatch   (``k+1`` single-token steps of the small draft
                      model, unrolled inside one XLA program)
  1 verify dispatch  (the target model over ``k+1`` rows per request,
                      write-then-attend through the paged block table)

and emits between 1 and ``k+1`` tokens per request — vs one target
dispatch per token.  The win is largest where per-dispatch overhead or
memory-bound decode dominates, exactly the serving decode hot loop.

At temperature > 0 (a sampling-mode engine) acceptance switches to
SPECULATIVE SAMPLING (the same papers' stochastic rule): the draft
samples each proposal from its warped distribution q, the target
accepts proposal ``x`` with probability ``min(1, p(x)/q(x))`` and the
first rejection resamples from the normalized residual
``max(p - q, 0)`` — the emitted stream is distribution-identical to
plain sampling from p, so the spec speedup extends to stochastic
traffic.  The whole acceptance chain runs inside the verify program
(``_build_verify`` with ``cfg.sampling``); the draft's q vectors ship
device-to-device from the draft dispatch and the host only ever syncs
the emitted rows.  Greedy rows (one-hot p and q) degenerate to the
argmax rule exactly, so a mixed batch needs no special casing.

The :class:`DraftWorker` here owns the draft side: the draft
checkpoint's parameters, its OWN (much smaller) paged K/V cache pair,
and the per-request ingest bookkeeping.  The draft cache shares the
target's block geometry and per-request block *tables* verbatim — the
target's ``BlockManager`` already guarantees table disjointness, so the
draft needs no block accounting of its own.  Draft-cache contents
affect ONLY the acceptance rate, never the output: correctness rides
entirely on the target's verify pass, which is why the draft side may
lazily re-ingest context (admission, preemption-resume, prefix-cache
hits) without any bitwise-reproducibility obligations.

Rollback: the verify pass writes target K/V for all ``k+1`` candidate
positions; after acceptance the engine truncates the request's block
table back to the accepted length (``BlockManager.truncate``) so
rejected drafts never hold cache blocks across iterations.  Stale K/V
*within* kept blocks is overwritten write-then-attend before any later
position can read it, the same argument that makes null-block garbage
safe.
"""

from __future__ import annotations

import collections
import threading

import jax
import jax.numpy as jnp

from ..base import env_float
from ..models.generate import (detect_gpt_variant, normalize_gpt_params,
                               reconcile_decode_config)
from ..telemetry import flight as flight_mod
from .programs import (_ModelCfg, _TableMix, _embed, _filter_logits,
                       _forward_token_batch, _jit_kwargs, _logits,
                       _logprob_outs, _operands, _outputs, _safe_log,
                       _sample, _stack)

__all__ = ["DraftWorker", "ENV_SPEC", "ENV_MIN_ACCEPT"]

ENV_SPEC = "MXTPU_SERVE_SPEC"
ENV_MIN_ACCEPT = "MXTPU_SPEC_MIN_ACCEPT"

# rolling acceptance-rate window length (verify events); the low-
# acceptance flight dump waits for MIN_WINDOW events before judging
WINDOW = 256
MIN_WINDOW = 32


class DraftWorker:
    """The draft-model half of speculative decoding.

    Owns the draft checkpoint's device-resident parameters and its own
    K/V cache pair shaped ``(draft_layers, num_blocks, block_size,
    draft_kv_heads, draft_head_dim)`` — the same block geometry as the
    target so the per-request block tables are shared verbatim.  All
    compiled draft programs resolve through the owning engine's program
    machinery (``_STEP_CACHE`` / AOT export store / warmup manifests),
    keyed ``kind="draft"`` (the k-step proposal loop, bucketed over the
    decode batch) and ``kind="draft_chunk"`` (context ingest, the chunk
    program built over the draft config).

    Mutable state is the per-request ingest ledger and the rolling
    acceptance window; both are read by ``/statusz`` scrapes from other
    threads, so mutations lock.
    """

    def __init__(self, engine, params, num_heads=None, window=None,
                 symbol=None, name="gpt"):
        if symbol is not None:
            num_heads, window = reconcile_decode_config(symbol, num_heads,
                                                        window)
        if num_heads is None:
            raise ValueError(
                "draft num_heads is required (pass draft_num_heads=, or "
                "draft_symbol= to read it from the draft's trained graph)")
        window = 0 if window is None else int(window)
        if window < 0:
            raise ValueError(f"draft window must be >= 0 (got {window})")
        params = normalize_gpt_params(params, name)
        spec = detect_gpt_variant(params, num_heads, name)
        if spec["vocab"] != engine.spec["vocab"]:
            raise ValueError(
                f"draft vocab ({spec['vocab']}) must match the target's "
                f"({engine.spec['vocab']}) — drafted token ids feed the "
                "target verify program directly")
        if (spec["pos_table"] is not None
                and spec["pos_table"] < engine.max_model_len):
            raise ValueError(
                f"draft positional table ({spec['pos_table']}) is shorter "
                f"than max_model_len ({engine.max_model_len}) — the draft "
                "must be able to read every position the target serves")
        self.name = name
        self.cfg = _ModelCfg(
            name=name, n_layers=spec["n_layers"],
            num_heads=int(num_heads), head_dim=spec["head_dim"],
            kv_heads=spec["kv_heads"], pos_table=spec["pos_table"],
            swiglu=spec["swiglu"], tied=spec["tied"],
            rmsnorm=spec["rmsnorm"], window=window,
            block_size=engine.block_size,
            # the draft cfg itself stays sampling=False: on a
            # sampling-mode engine the draft program's warp/operand
            # layout rides the TARGET cfg (``_build_draft(sample_cfg=)``
            # — keyed by the engine cfg in _spec_key either way), and
            # the draft_chunk ingest program never samples at all.
            # The draft cache stays fp even under MXTPU_SERVE_KV_DTYPE=
            # int8: it is small by design, and draft-cache contents
            # only ever move the acceptance rate, never a token
            sampling=False, sample_cap=0, numeric_watch=False,
            kv_quant=False)
        # place the draft weights; under tensor parallelism they
        # replicate (the draft is small by design — sharding it would
        # buy latency nothing and complicate the program cache keys)
        rep = (engine._shardings.rep if engine._shardings is not None
               else None)
        self._owned = []
        placed = {}
        for k, v in params.items():
            arr = (jax.device_put(v, rep) if rep is not None
                   else jnp.asarray(v))
            if arr is not v:
                self._owned.append(arr)
            placed[k] = arr
        self.params = placed
        dt = self.params[f"{name}_tok_embed_weight"].dtype
        shape = (spec["n_layers"], engine.num_blocks, engine.block_size,
                 spec["kv_heads"], spec["head_dim"])
        self.cache_k = jnp.zeros(shape, dt)
        self.cache_v = jnp.zeros(shape, dt)
        self.min_accept = env_float(ENV_MIN_ACCEPT, 0.0)
        self._lock = threading.Lock()
        # rid -> (preemption epoch, draft-valid positions): which
        # prefix of the request's context the draft cache holds.  A
        # resume-by-recomputation bumps the epoch, forcing a full
        # re-ingest into the request's NEW block table.
        self._valid = {}                          # guarded-by: _lock
        # rolling (k, accepted) per verify — the statusz acceptance
        # window and the low-acceptance anomaly trigger
        self._window = collections.deque(maxlen=WINDOW)  # guarded-by: _lock

    # -- context ingest ------------------------------------------------------
    def context_gap(self, req):
        """Positions ``[0, req.cache_len)`` the draft cache does NOT
        yet hold for ``req`` (0 when drafting can start right away)."""
        with self._lock:
            state = self._valid.get(req.rid)
        if state is not None and state[0] == req.n_preemptions \
                and state[1] >= req.cache_len:
            return 0
        return int(req.cache_len)

    def note_ingested(self, req, n_positions):
        with self._lock:
            self._valid[req.rid] = (req.n_preemptions, int(n_positions))

    def note_drafted(self, req, n_positions):
        """The draft program just wrote K/V through ``n_positions``
        (the k-step loop writes every candidate position, so the next
        iteration never has an ingest gap whatever was accepted)."""
        self.note_ingested(req, n_positions)

    def forget(self, rid):
        """Request left the engine (finished/cancelled): drop its
        ingest ledger entry so the table stays bounded by the number of
        in-flight requests."""
        with self._lock:
            self._valid.pop(rid, None)

    def prune(self, live_rids):
        """Drop ledger entries for rids no longer running — requests
        that left the engine between decode iterations (preempted then
        rejected/cancelled) never pass the per-batch ``forget`` path,
        and the table must stay bounded by the live running set."""
        with self._lock:
            for rid in [r for r in self._valid if r not in live_rids]:
                del self._valid[rid]

    # -- acceptance accounting ----------------------------------------------
    def on_verify(self, k, accepted):
        """One verify pass proposed ``k`` tokens and the target
        accepted ``accepted``.  Feeds the rolling window; when the
        windowed rate sits below ``MXTPU_SPEC_MIN_ACCEPT`` the flight
        recorder dumps (rate-limited per reason) — a silently diverging
        draft is a perf regression nobody sees in correctness tests."""
        with self._lock:
            self._window.append((int(k), int(accepted)))
            rate = self._window_rate_locked()
            n = len(self._window)
        if (self.min_accept > 0.0 and n >= MIN_WINDOW
                and rate is not None and rate < self.min_accept):
            flight_mod.recorder().dump(
                "spec_low_acceptance",
                extra={"accept_rate": round(rate, 4),
                       "threshold": self.min_accept, "window": n})

    def _window_rate_locked(self):
        drafted = sum(k for k, _ in self._window)
        if not drafted:
            return None
        return sum(a for _, a in self._window) / drafted

    def accept_rate_window(self):
        """Acceptance rate over the rolling window (None before any
        verify)."""
        with self._lock:
            rate = self._window_rate_locked()
        return None if rate is None else round(rate, 4)

    # -- introspection -------------------------------------------------------
    def statusz(self, engine):
        """The engine's ``/statusz`` ``spec`` section."""
        cfg = self.cfg
        with self._lock:
            window_n = len(self._window)
            rate = self._window_rate_locked()
            tracked = len(self._valid)
        rate_greedy, rate_stochastic = engine._stats.spec_mode_rates()
        return {
            "k": engine.spec_k,
            # the greedy-vs-stochastic acceptance split (rejection-
            # sampled verifies vs exact argmax ones) — the SAME
            # formula ServeStats.snapshot reads, so the views cannot
            # drift
            "accept_rate_greedy": rate_greedy,
            "accept_rate_stochastic": rate_stochastic,
            "draft": {
                "name": self.name,
                "n_layers": cfg.n_layers,
                "d_model": cfg.num_heads * cfg.head_dim,
                "kv_heads": cfg.kv_heads,
                "params_bytes": sum(int(v.nbytes)
                                    for v in self.params.values()),
                "kv_cache_bytes": 2 * int(self.cache_k.nbytes),
            },
            "accept_rate_window": (None if rate is None
                                   else round(rate, 4)),
            "window_verifies": window_n,
            "min_accept": self.min_accept,
            "tracked_requests": tracked,
            "verify_buckets": engine.verify_buckets(),
        }

    def shutdown(self):
        """Release the draft-side device buffers (mirrors
        ``Engine.shutdown``'s exactly-what-we-placed policy)."""
        for arr in self._owned + [self.cache_k, self.cache_v]:
            try:
                arr.delete()
            except (RuntimeError, ValueError):
                pass              # already donated-away or deleted
        self._owned = []
        self.cache_k = self.cache_v = None
        self.params = None
        with self._lock:
            self._valid.clear()


# -- acceptance rule (host-side, pure) ---------------------------------------
def accept_greedy(drafted_row, target_row, k):
    """Greedy acceptance for one request: ``drafted_row`` holds the k
    drafted tokens, ``target_row`` the target's k+1 argmax tokens (row
    j scored after consuming row j's input).  Returns ``(accepted,
    emit)``: the agreeing-prefix length and the tokens to emit — the
    accepted drafts plus the target's own token at the first
    disagreement (or its bonus token when everything agreed).  The
    emitted stream is exactly what plain greedy decode would produce.
    """
    a = 0
    while a < k and int(drafted_row[a]) == int(target_row[a]):
        a += 1
    return a, [int(x) for x in drafted_row[:a]] + [int(target_row[a])]


# -- compiled-program bodies -------------------------------------------------
def _build_draft(cfg, k, donate, shardings=None, sample_cfg=None):
    """The k-step draft-proposal program (kind="draft", bucketed over
    the decode batch).  Unrolls ``k+1`` single-token steps of the draft
    model inside ONE jit: step ``j`` writes the fed token's K/V at
    ``pos+j`` through the (target-shared) block table, attends via
    ``paged_attention``, and its proposal feeds step ``j+1``.  Steps
    ``0..k-1`` produce the k drafted tokens; step ``k`` is write-only —
    it parks the last draft's K/V so the next iteration never has an
    ingest gap even when every draft is accepted (its logits head is
    dead code XLA eliminates).

    With ``sample_cfg`` (the TARGET engine's sampling-mode cfg) each
    step SAMPLES its proposal from the draft's warped distribution q
    — per-request (B,)-shaped temperature/top-p/top-k operands, the
    same warp the target applies — and the program additionally
    returns q in CANDIDATE space: the sampled token's own probability
    ``q_at (B, k)`` plus the per-step candidate probabilities and
    vocab ids ``(B, k, cap)`` pairs.  That is everything the verify
    program's rejection-sampling acceptance ever evaluates q at (the
    drafted tokens and the target's own candidate ids), shipped
    device-to-device at ``cap``-width instead of a dense ``(B, k,
    vocab)`` tensor — on a 50k vocab that is ~400x less inter-dispatch
    HBM traffic on the decode hot path.  Without it (greedy engines)
    the proposal is the historical argmax, byte-for-byte.
    """
    def draft(params, ck, cv, toks, pos, tables, rng):
        S = tables.shape[1] * cfg.block_size
        cur = toks
        outs = []
        for j in range(k + 1):
            # a step past the table's last slot writes to the null
            # block (zeroed table row) instead of clamp-aliasing onto
            # the request's last real block; the row's own output is
            # garbage, but it can only ever be a beyond-quota draft
            # the verify-side emit cap drops
            tbl = jnp.where((pos + j < S)[:, None], tables, 0)
            logits, ck, cv, _, _ = _forward_token_batch(
                cfg, params, ck, cv, None, None, cur, pos + j, tbl,
                shardings=shardings)
            if j < k:
                cur = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                outs.append(cur)
        return jnp.stack(outs, axis=1), ck, cv

    def draft_rs(params, ck, cv, toks, pos, tables, temp, topp, topk,
                 rng):
        S = tables.shape[1] * cfg.block_size
        keys = jax.random.split(rng, k)
        cur = toks
        outs, q_at, q_vals, q_idx = [], [], [], []
        for j in range(k + 1):
            tbl = jnp.where((pos + j < S)[:, None], tables, 0)
            logits, ck, cv, _, _ = _forward_token_batch(
                cfg, params, ck, cv, None, None, cur, pos + j, tbl,
                shardings=shardings)
            if j < k:
                # sample the proposal from the warped draft
                # distribution and keep that EXACT distribution —
                # q(x) of min(1, p/q) acceptance — as the candidate
                # (probability, vocab-id) pairs plus the sampled
                # token's own q
                masked, idx = _filter_logits(sample_cfg, logits, temp,
                                             topp, topk)
                probs = jax.nn.softmax(masked, axis=-1)
                choice = jax.random.categorical(keys[j], masked,
                                                axis=-1)
                cur = jnp.take_along_axis(
                    idx, choice[..., None],
                    axis=-1)[..., 0].astype(jnp.int32)
                outs.append(cur)
                q_at.append(jnp.take_along_axis(
                    probs, choice[..., None], axis=-1)[..., 0])
                q_vals.append(probs)
                q_idx.append(idx)
        return (jnp.stack(outs, axis=1), jnp.stack(q_at, axis=1),
                jnp.stack(q_vals, axis=1), jnp.stack(q_idx, axis=1),
                ck, cv)

    sampling = sample_cfg is not None
    kw = {"donate_argnums": (1, 2) if donate else ()}
    if shardings is not None:
        rep = shardings.rep
        kw["in_shardings"] = (rep,) * (10 if sampling else 7)
        kw["out_shardings"] = (rep,) * (6 if sampling else 3)
    return jax.jit(draft_rs if sampling else draft, **kw)


def _build_verify(cfg, k, donate, shardings=None):
    """The target-model verify program (kind="verify", bucketed over
    the decode batch; ``k`` is static config).  Scores ``k+1`` rows per
    request — the last emitted token plus the k drafts — through the
    paged block table in one dispatch, the chunk program's
    write-then-attend with a request axis (``programs._TableMix``), so
    a verify row's logits track what the single-token decode program
    would compute for the same context.

    On a sampling-mode engine (``cfg.sampling``) the program ALSO owns
    acceptance: rejection sampling (Leviathan et al. 2023; Chen et al.
    2023) entirely on device.  With p the target's warped distribution
    at each position and q the draft's (shipped in as ``(B, k, V)``
    operands straight off the draft dispatch), draft j is accepted
    with probability ``min(1, p(x_j)/q(x_j))``; the first rejection
    resamples from the normalized residual ``max(p - q, 0)`` and a
    fully-accepted run samples a bonus token from the last row's p.
    The emitted prefix is distribution-identical to sampling from p
    token by token — whatever the draft proposed — and greedy rows
    (one-hot p and q) degenerate to exact argmax-prefix acceptance.
    Outputs: the emit rows ``(B, k+1)``, accepted counts ``(B,)`` and
    the emitted tokens' logprob views, so the host's only sync is the
    result.
    """
    K1 = k + 1

    def verify(params, *rest):
        """``rows`` (B, K1) int32 token ids; ``pos0`` (B,) the cache
        position of each request's row 0; ``tables`` (B, W).  Returns
        the target's (B, K1) greedy tokens (row j's token decided after
        consuming rows 0..j) — or, in sampling mode, the
        rejection-sampled emit rows + accepted counts + logprobs."""
        adp, caches, host, slots, sampling = _operands(
            cfg, rest, 7 if cfg.sampling else 3)
        if cfg.sampling:
            toks0, drafted, q_at, q_vals, q_idx, pos0, tables = host
            rows = jnp.concatenate([toks0[:, None], drafted], axis=1)
        else:
            rows, pos0, tables = host
        B = rows.shape[0]
        pos = pos0[:, None] + jnp.arange(K1)[None, :]      # (B, K1)
        x = _embed(cfg, params, rows, pos, clamp=True)     # (B, K1, D)
        S = tables.shape[1] * cfg.block_size
        # candidate rows past the request's final position (a quota-
        # capped last iteration) write to the NULL block: a clamped
        # gather would alias them onto the LAST table slot and clobber
        # real K/V.  Null-block garbage is never read back — the
        # causal mask only admits logical positions backed by real
        # blocks — and the emit cap drops those rows' tokens anyway.
        bidx = jnp.minimum(pos // cfg.block_size, tables.shape[1] - 1)
        blk = jnp.where(pos < S,
                        jnp.take_along_axis(tables, bidx, axis=1), 0)
        mix = _TableMix(cfg, caches, pos, tables, blk,
                        pos % cfg.block_size, pos0)
        x = _stack(cfg, params, x, mix, adp, slots)
        logits = _logits(cfg, params, x)                   # (B, K1, V)
        if cfg.sampling:
            # -- rejection-sampling acceptance, on device --------------
            # everything runs in CANDIDATE space (sample_cap wide,
            # never vocab-wide): the residual max(p - q, 0) is
            # supported only where p > 0, i.e. inside the target's
            # candidate set, so neither distribution materializes a
            # full-vocab vector — q arrives as the draft's candidate
            # (probability, id) pairs and is re-evaluated at the
            # target's candidate ids by id matching
            temp, topp, topk, rng = sampling
            kacc, kres, kbonus = jax.random.split(rng, 3)
            # p: the target's warped sampling distribution per row
            # (operands broadcast over the K1 axis); greedy rows are
            # exactly one-hot, so accept degenerates to argmax match
            masked_p, idx_p = _filter_logits(
                cfg, logits, temp[:, None], topp[:, None],
                topk[:, None])                           # (B, K1, cap)
            p_cand = jax.nn.softmax(masked_p, axis=-1)
            idx_k = idx_p[:, :K1 - 1]                    # (B, k, cap)
            # p(x_j): x_j's probability under the target's filtered
            # distribution (0 when the draft proposed outside the
            # target's candidate set); q(x_j) shipped from the draft
            p_at = jnp.sum(
                jnp.where(idx_k == drafted[..., None],
                          p_cand[:, :K1 - 1], 0.0), axis=-1)
            u = jax.random.uniform(kacc, drafted.shape)
            # u < min(1, p/q)  <=>  u*q < p (q(x_j) > 0: x_j was
            # sampled from q)
            accept = u * q_at < p_at
            acc = jnp.sum(jnp.cumprod(accept.astype(jnp.int32),
                                      axis=1), axis=1)     # (B,)
            # the first rejection resamples from the normalized
            # residual max(p - q, 0) — together with the acceptance
            # rule this reproduces p exactly (Leviathan 2023, Thm 1).
            # An identically-zero residual means p == q: acceptance
            # was certain there, the row is never read — substitute p
            # to keep the categorical well-defined
            # q at the TARGET's candidate ids, by id matching the
            # draft's candidate pairs (candidate ids are unique per
            # row, so at most one match contributes)
            q_cand = jnp.sum(
                jnp.where(idx_k[..., :, None] == q_idx[..., None, :],
                          q_vals[..., None, :], 0.0), axis=-1)
            res = jnp.maximum(p_cand[:, :K1 - 1] - q_cand, 0.0)
            rsum = jnp.sum(res, axis=-1, keepdims=True)
            res = jnp.where(rsum > 0, res / rsum, p_cand[:, :K1 - 1])
            corr_c = jax.random.categorical(kres, _safe_log(res),
                                            axis=-1)       # (B, k)
            corr = jnp.take_along_axis(
                idx_k, corr_c[..., None], axis=-1)[..., 0]
            # the bonus token samples from the last row's p directly
            # (categorical over the masked logits IS sampling from p;
            # greedy rows pick candidate 0 — the argmax — exactly)
            bonus_c = jax.random.categorical(kbonus, masked_p[:, K1 - 1],
                                             axis=-1)
            bonus = jnp.take_along_axis(
                idx_p[:, K1 - 1], bonus_c[..., None], axis=-1)[..., 0]
            first_rej = jnp.minimum(acc, K1 - 2)
            corr_at = jnp.take_along_axis(
                corr, first_rej[:, None], axis=1)[:, 0]
            fixed = jnp.where(acc < K1 - 1, corr_at,
                              bonus).astype(jnp.int32)
            jj = jnp.arange(K1)[None, :]
            pad = jnp.concatenate(
                [drafted, jnp.zeros((B, 1), jnp.int32)], axis=1)
            emit = jnp.where(jj < acc[:, None], pad,
                             fixed[:, None]).astype(jnp.int32)
            outs = (emit, acc.astype(jnp.int32)) \
                + _logprob_outs(logits, emit)
        else:
            outs = (_sample(cfg, logits, *sampling),)
        return _outputs(cfg, outs, logits, mix.caches)

    return jax.jit(verify, **_jit_kwargs(
        cfg, donate, shardings, 7 if cfg.sampling else 3,
        n_lead=5 if cfg.sampling else None))
