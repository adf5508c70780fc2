"""What the compiled serve programs of a gpt() decoder are made of:
pure functions of an immutable :class:`_ModelCfg` (never an ``Engine``:
the shared ``_STEP_CACHE`` must not retain a retired engine's parameter
dict).  The arrows: ``engine -> {spec, hybrid} -> programs -> ops``.

ONE decoder layer, :func:`layer`, stands under every program.  The
pass's *mixer context* carries the caches and the pass's operands,
writes the rows' K/V (``_Mix._kv_write``, quantised or not) and
attends: :class:`_DecodeMix` through ``paged_attention``,
:class:`_PromptMix` and :class:`_TableMix` through
``ops.attention.masked_attention``, the one span attention.  One
prologue and one epilogue (``_operands``, ``_finish``) frame decode,
prefill and chunk here, verify in ``serve/spec.py``; ``serve/hybrid.py``
shares the epilogue and the span attention.

Every program passes the stacked cache whole and names the layer by a
static index (``layer=i``, ``ck[i, table]``), never ``ck[i]``, which on
the chip is a copy of that layer's whole pool.
"""

from __future__ import annotations

import collections

import numpy as np

import jax
import jax.numpy as jnp

from ..models.generate import _fc, _gelu, _ln
from ..ops.attention import masked_attention, paged_attention, score_scale

# the static model config the compiled programs close over
# (numeric_watch is part of it: the watchdog variant returns an extra
# logits-finite flag, so it is a DIFFERENT compiled program and a
# different AOT artifact; kv_quant likewise — the int8-KV variant
# threads two scale arrays through every program.  kv_quant=False is
# REMOVED from the AOT fingerprint dict so a quant-off engine keeps
# its pre-quant digests — see _aot_base_fp).
# ``sampling``/``sample_cap`` replace the old per-engine
# temperature/top_k TRACE KEYS: sampling params are per-request
# (B,)-shaped OPERANDS of the sampling-mode programs, so one program
# per bucket serves any mix of temperature/top-p/top-k with zero
# retraces.  sampling=False is the historical greedy program,
# byte-for-byte (and _aot_base_fp re-emits the historical
# temperature=0.0/top_k=None fingerprint fields for it).
_ModelCfg = collections.namedtuple("_ModelCfg", [
    "name", "n_layers", "num_heads", "head_dim", "kv_heads",
    "pos_table", "swiglu", "tied", "rmsnorm", "window", "block_size",
    "sampling", "sample_cap", "numeric_watch", "kv_quant",
    # paged LoRA multiplexing (serve/adapters.py): slot count and the
    # padded rank ceiling.  adapters=0 (off, the default) follows the
    # sampling precedent — both fields leave the AOT fingerprint so an
    # adapters-off engine keeps its historical digests
    "adapters", "adapter_rank",
    # hybrid decoders (serve/hybrid.py): the decoder's description and
    # each layer's place in its kind's cache stack.  None (every gpt()
    # engine) follows the same only-when-on rule and leaves the AOT
    # fingerprint, so the gpt programs keep their digests
    "hybrid"],
    defaults=(0, 0, None))

# top-logprob candidates every sampling-mode program returns per
# sampled position (static — the per-request ``logprobs`` count only
# selects how many of them the host surfaces)
TOP_LOGPROBS = 5


def _cfg_fp_fields(cfg):
    """``_ModelCfg`` -> AOT-fingerprint fields.  The sampling-mode
    fields follow the only-when-on rule: a sampling-off cfg re-emits
    the historical ``temperature=0.0``/``top_k=None`` trace-key fields
    (dropping sampling/sample_cap), so a greedy engine's digests are
    byte-identical to pre-operand releases and an upgraded greedy
    fleet keeps loading its existing artifacts and manifests."""
    d = dict(cfg._asdict())
    if not d.get("sampling"):
        d.pop("sampling", None)
        d.pop("sample_cap", None)
        d["temperature"] = 0.0
        d["top_k"] = None
    if not d.get("adapters"):
        # same only-when-on rule: adapters-off keeps pre-LoRA digests
        d.pop("adapters", None)
        d.pop("adapter_rank", None)
    if d.get("hybrid") is None:
        d.pop("hybrid", None)
    else:
        # JSON-stable: the description's fields (the stack indices are
        # derived from layer_types)
        dec = d["hybrid"].dec._asdict()
        dec["layer_types"] = list(dec["layer_types"])
        d["hybrid"] = dec
    return d


def _rope(u, pos, base=10000.0, inv=None, rot=None, scale=None):
    """Rotate rows ``(..., H, Dh)`` by their own positions ``(...,)`` —
    matches ops/attention.py RoPEOp / generate.py's scalar-position _rot.
    ``inv``: a table of ``rot / 2`` inverse frequencies in place of
    ``base``'s; ``rot``: only the first ``rot`` of the ``Dh`` dimensions
    turn, the rest pass through; ``scale`` multiplies cosines and sines
    (models/moe.py's ``Rope``).  Without them: the whole head at
    ``base``."""
    if pos.ndim != 1:          # verify's (B, k+1) rows: as one flat list
        return _rope(u.reshape((-1,) + u.shape[-2:]), pos.reshape(-1),
                     base, inv, rot, scale).reshape(u.shape)
    width = u.shape[-1] if rot is None else rot
    half = width // 2
    if inv is None:
        inv = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv          # (N, half)
    cos = jnp.cos(ang)[:, None, :]
    sin = jnp.sin(ang)[:, None, :]
    if scale is not None:
        cos, sin = cos * np.float32(scale), sin * np.float32(scale)
    uf = u.astype(jnp.float32)
    u1, u2 = uf[..., :half], uf[..., half:width]
    return jnp.concatenate([u1 * cos - u2 * sin, u1 * sin + u2 * cos]
                           + ([] if width == u.shape[-1]
                              else [uf[..., width:]]),
                           axis=-1).astype(u.dtype)


# -- quantized serving helpers ------------------------------------------------
def _quantize_gpt_params(params, name, spec):
    """Weight-only int8 at load: every matmul projection of the
    normalized gpt() checkpoint gets per-output-channel symmetric int8
    weights (``contrib.quantization.quantize_weight``) plus a
    ``*_wscale`` f32 vector that ``_wfc`` dequantizes on the fly —
    4x smaller weight reads on the decode hot loop, the
    ``ops/quantized.py`` weight-only convention.  Embeddings, norms
    and biases stay fp; a tied LM head IS the embedding matrix, so it
    stays fp too (quantizing it would also perturb every input
    embedding lookup)."""
    from ..contrib.quantization import quantize_weight

    out = dict(params)
    stems = []
    for i in range(spec["n_layers"]):
        p = f"{name}_l{i}"
        stems += [f"{p}_q", f"{p}_k", f"{p}_v", f"{p}_proj",
                  f"{p}_ff_up", f"{p}_ff_down"]
        if spec["swiglu"]:
            stems.append(f"{p}_ff_gate")
    if not spec["tied"]:
        stems.append(f"{name}_head")
    for stem in stems:
        w = out.get(f"{stem}_weight")
        if w is None:
            continue
        # mxtpu-lint: disable=host-sync (load path, runs once at
        # engine construction: the checkpoint must reach the host to
        # quantize before placement)
        wq, sc = quantize_weight(np.asarray(w, np.float32))
        out[f"{stem}_weight"] = wq
        out[f"{stem}_wscale"] = sc
    return out


def _wfc(params, stem, x):
    """``_fc`` through a possibly weight-only-int8 checkpoint entry:
    when ``<stem>_wscale`` exists the int8 weight dequantizes on the
    fly (``ops/quantized.py``'s weight-only mode — activation-dtype
    math, 4x smaller weight reads); without it this is exactly
    ``_fc`` on the fp entry, so quant-off traced programs are
    byte-for-byte what they were before quantized serving existed."""
    w = params[f"{stem}_weight"]
    sc = params.get(f"{stem}_wscale")
    if sc is not None:
        w = w.astype(x.dtype) * sc.astype(x.dtype)[:, None]
    return _fc(x, w, params[f"{stem}_bias"])


def _lora_delta(adp, stem, x, slots):
    """The paged-LoRA low-rank delta for one projection: gather each
    row's (A, B) slices from the device stacks by its slot operand and
    compute ``scale * x @ A.T @ B.T`` — never materializing a merged
    weight.  Slot 0's rows and scale are true zeros, so base rows add
    exactly ``+0.0`` (token-identical to an adapters-off engine).

    ``slots`` is a scalar for the one-request prefill/chunk programs,
    ``(B,)`` for decode (2-D ``x``) and verify (3-D ``(B, K+1, D)``
    ``x`` — the slot broadcasts over the candidate positions)."""
    a = adp[f"{stem}_A"].astype(x.dtype)          # (S, r, d_in)
    b = adp[f"{stem}_B"].astype(x.dtype)          # (S, d_out, r)
    sc = adp["scale"]
    if slots.ndim == 0:
        u = x @ a[slots].T                        # (..., r)
        return (u @ b[slots].T) * sc[slots].astype(x.dtype)
    ga, gb = a[slots], b[slots]
    s = sc[slots].astype(x.dtype)
    if x.ndim == 2:
        u = jnp.einsum("bi,bri->br", x, ga)
        return jnp.einsum("br,bor->bo", u, gb) * s[:, None]
    u = jnp.einsum("bki,bri->bkr", x, ga)
    return jnp.einsum("bkr,bor->bko", u, gb) * s[:, None, None]


def _awfc(cfg, params, adp, stem, x, slots):
    """:func:`_wfc` plus the request's LoRA delta when the program
    threads the adapter stacks.  ``adp`` is None on adapters-off
    engines — a Python-level branch, so their traced programs stay
    byte-for-byte the historical ones."""
    base = _wfc(params, stem, x)
    if adp is None:
        return base
    return base + _lora_delta(adp, stem, x, slots)


def _kv_quant_vals(vals):
    """Per-slot-per-head symmetric int8 for K/V rows ``(..., Hkv, Dh)``
    -> ``(int8 rows, f32 scales (..., Hkv))``.  Each written slot
    quantizes independently over its own head vector, so the cache
    contents are a pure function of the fp values written — write
    ORDER cannot change them, which is what keeps preemption-by-
    recomputation and chunked re-prefill token-stable under int8 KV
    (a block-granular scale would re-scale earlier slots on every
    later write).  Zero vectors keep scale 1.0, ``quantize_weight``'s
    convention, so untouched cache stays exactly zero."""
    vf = vals.astype(jnp.float32)
    amax = jnp.max(jnp.abs(vf), axis=-1)
    sc = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(vf / sc[..., None]), -127, 127).astype(jnp.int8)
    return q, sc


def _kv_dequant(q, sc, dtype):
    """Invert :func:`_kv_quant_vals`: ``(..., Hkv, Dh)`` int8 plus
    ``(..., Hkv)`` scales -> fp rows in ``dtype``."""
    return (q.astype(jnp.float32)
            * sc.astype(jnp.float32)[..., None]).astype(dtype)


# -- sampling -----------------------------------------------------------------
def _sample(cfg, logits, key):
    """Greedy argmax — the sampling-OFF programs' sampler, exactly the
    historical temperature-0 path (``key`` stays in the signature so
    the greedy program's operand list never moves).  Stochastic
    serving threads per-request operands through :func:`_sample_ops`
    inside the sampling-mode programs instead — temperature/top-k are
    no longer trace keys anywhere."""
    del key
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


# -- operand sampling (the sampling-mode programs' warp + sample) ------------
def _filter_logits(cfg, logits, temp, top_p, top_k):
    """Temperature/top-k/top-p warping with PER-ROW traced operands.

    ``logits`` (..., V); ``temp``/``top_p`` f32 and ``top_k`` int32
    broadcastable over the leading dims (0 = filter off for top_k).
    Returns ``(masked, idx)``: the top-``sample_cap`` candidates'
    warped logits (filtered positions at -inf) in descending order,
    and their vocab ids.  ``jax.lax.top_k`` replaces the old
    full-vocab ``jnp.sort``: the kth-largest threshold only ever
    needs the leading ``cap`` candidates, and top-p needs the same
    descending slice — one top_k call serves both (numerical
    equivalence vs the sort formulation is pinned in
    tests/test_sampling.py).  Candidates past the cap are never
    sampled — the cap itself acts as a top-``cap`` filter (exact
    whenever cap >= vocab, e.g. the tiny-vocab statistical pins).
    Greedy rows (temp <= 0) come out one-hot on the argmax, so a
    categorical draw over ``masked`` IS argmax there — every other
    candidate sits at -inf.
    """
    V = logits.shape[-1]
    cap = min(cfg.sample_cap, V) if cfg.sample_cap else V
    greedy = temp <= 0.0
    lg = logits.astype(jnp.float32)
    scaled = lg / jnp.where(greedy, 1.0, temp)[..., None]
    vals, idx = jax.lax.top_k(scaled, cap)             # descending
    # fence the sort's outputs: XLA-CPU's producer-duplicating fusion
    # otherwise re-runs the whole top-k sort inside every consumer of
    # ``idx`` (measured 15x on the verify program's acceptance gather)
    vals, idx = jax.lax.optimization_barrier((vals, idx))
    j = jnp.arange(cap)
    k_eff = jnp.where(top_k > 0, jnp.minimum(top_k, cap), cap)
    keep = j < k_eff[..., None]
    probs = jax.nn.softmax(jnp.where(keep, vals, -jnp.inf), axis=-1)
    csum = jnp.cumsum(probs, axis=-1)
    # nucleus: the smallest candidate set whose mass reaches top_p —
    # a candidate stays while the mass BEFORE it is under top_p
    keep = jnp.logical_and(keep, (csum - probs) < top_p[..., None])
    masked = jnp.where(keep, vals, -jnp.inf)
    return jnp.where(greedy[..., None],
                     jnp.where(j == 0, 0.0, -jnp.inf), masked), idx


def _sample_ops(cfg, logits, key, temp, top_p, top_k):
    """Sample one token per row from the warped distribution (greedy
    rows are exact argmax); int32 ids of the leading shape."""
    masked, idx = _filter_logits(cfg, logits, temp, top_p, top_k)
    choice = jax.random.categorical(key, masked, axis=-1)
    return jnp.take_along_axis(
        idx, choice[..., None], axis=-1)[..., 0].astype(jnp.int32)


def _scatter_probs(probs, idx, V):
    """Scatter per-candidate probabilities ``(..., cap)`` back onto
    their vocab ids -> a full ``(..., V)`` probability vector (zeros
    off the candidate set)."""
    lead = probs.shape[:-1]
    flat_p = probs.reshape((-1, probs.shape[-1]))
    flat_i = idx.reshape((-1, idx.shape[-1]))
    n = flat_p.shape[0]
    full = jnp.zeros((n, V), jnp.float32).at[
        jnp.arange(n)[:, None], flat_i].set(flat_p)
    return full.reshape(lead + (V,))


def _filtered_probs_full(cfg, logits, temp, top_p, top_k):
    """The warped SAMPLING distribution as a full-vocab probability
    vector ``(..., V)`` — the REFERENCE view of the warp, used by the
    test suite's sort-equivalence and distribution pins.  The serving
    hot path never materializes it: the programs sample straight from
    the candidate representation (`_filter_logits` + categorical) and
    the verify program's rejection-sampling acceptance evaluates p and
    q purely at candidate ids (serve/spec.py)."""
    masked, idx = _filter_logits(cfg, logits, temp, top_p, top_k)
    return _scatter_probs(jax.nn.softmax(masked, axis=-1), idx,
                          logits.shape[-1])


def _safe_log(p):
    """log(p) with exact -inf at p == 0 (a zero-probability token can
    never win a categorical draw, and a one-hot row samples its hot
    token deterministically)."""
    return jnp.where(p > 0, jnp.log(jnp.maximum(p, 1e-38)), -jnp.inf)


def _logprob_outs(logits, toks):
    """The logprob outputs every sampling-mode program returns for its
    sampled positions: the chosen token's log-softmax plus the
    ``TOP_LOGPROBS`` best candidates (values + ids).  RAW model
    logprobs (pre-temperature/filtering, the OpenAI-style convention)
    — greedy and stochastic rows report the same quantity."""
    lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    chosen = jnp.take_along_axis(
        lp, toks[..., None].astype(jnp.int32), axis=-1)[..., 0]
    tv, ti = jax.lax.top_k(lp, min(TOP_LOGPROBS, lp.shape[-1]))
    return chosen, tv, ti.astype(jnp.int32)


def _mlp(cfg, params, p, x, adp=None, slots=None):
    h2 = _ln(x, params[f"{p}_ln2_gamma"],
             None if cfg.rmsnorm else params[f"{p}_ln2_beta"])
    if cfg.swiglu:
        g = _awfc(cfg, params, adp, f"{p}_ff_gate", h2, slots)
        gf = g.astype(jnp.float32)               # f32 silu == sym.silu
        up = ((gf * jax.nn.sigmoid(gf)).astype(g.dtype)
              * _awfc(cfg, params, adp, f"{p}_ff_up", h2, slots))
    else:
        up = _gelu(_awfc(cfg, params, adp, f"{p}_ff_up", h2, slots))
    return _awfc(cfg, params, adp, f"{p}_ff_down", up, slots)


def _logits(cfg, params, x):
    name = cfg.name
    final = _ln(x, params[f"{name}_ln_f_gamma"],
                None if cfg.rmsnorm else params[f"{name}_ln_f_beta"])
    if cfg.tied:
        return final @ params[f"{name}_tok_embed_weight"].T.astype(
            final.dtype)
    return _wfc(params, f"{name}_head", final)


def _embed(cfg, params, toks, pos, clamp=False):
    """Token rows -> hidden rows, plus the position table's rows at
    ``pos`` (an array or a slice; ``clamp``: padded rows may exceed it)."""
    x = params[f"{cfg.name}_tok_embed_weight"][toks]
    if cfg.pos_table is not None:
        if clamp:
            pos = jnp.minimum(pos, cfg.pos_table - 1)
        x = x + params[f"{cfg.name}_pos_embed_weight"][0, pos]
    return x


# -- mixer contexts: what a pass hands the layer ------------------------------
def _head_shard_kw(shardings):
    """What ``paged_attention`` and ``masked_attention`` need of a tp
    placement bundle: the mesh and the axis the cache's heads are split
    over, so that a Mosaic kernel runs per head shard."""
    if shardings is None:
        return {}
    cache_spec = shardings.cache.spec               # (L, nb, bs, Hkv, Dh)
    return {"mesh": shardings.mesh,
            "head_axis": cache_spec[3] if len(cache_spec) > 3 else None}


class _Mix:
    """A pass's caches (``ck, cv``, then the scales ``ksc, vsc`` of int8
    caches), its rows' positions ``pos`` and the slot ``(blk, off)`` each
    row's K/V goes to.  Built once a trace; ``attend(i, qh, kh, vh)``
    writes layer ``i``'s K/V and returns the rows' attention output."""

    def __init__(self, cfg, caches, pos, blk, off):
        self.cfg, self.caches = cfg, tuple(caches)
        self.pos, self.blk, self.off = pos, blk, off

    def _kv_write(self, i, kh, vh, read_back=False):
        """Layer ``i``'s K/V rows into the caches.  ``read_back``:
        return them as the cache now holds them (an int8 round trip)."""
        at = (i, self.blk, self.off)
        if not self.cfg.kv_quant:
            ck, cv = self.caches
            self.caches = (ck.at[at].set(kh), cv.at[at].set(vh))
            return kh, vh
        ck, cv, ksc, vsc = self.caches
        kq, ks = _kv_quant_vals(kh)
        vq, vs = _kv_quant_vals(vh)
        ck, ksc = ck.at[at].set(kq), ksc.at[at].set(ks)
        cv, vsc = cv.at[at].set(vq), vsc.at[at].set(vs)
        self.caches = (ck, cv, ksc, vsc)
        if read_back:
            return (_kv_dequant(kq, ks, kh.dtype),
                    _kv_dequant(vq, vs, vh.dtype))


class _DecodeMix(_Mix):
    """One position for each of B rows, through its block table.
    ``shardings`` (the program's tp placement bundle) gives
    ``paged_attention`` the mesh and the axis the cache's heads are
    split over, so the Mosaic kernel runs per head shard."""

    def __init__(self, cfg, caches, pos, tables, shardings=None):
        blk = jnp.take_along_axis(tables, (pos // cfg.block_size)[:, None],
                                  axis=1)[:, 0]
        super().__init__(cfg, caches, pos, blk, pos % cfg.block_size)
        self.tables, self.ctx = tables, pos + 1
        self.paged_kw = _head_shard_kw(shardings)

    def attend(self, i, qh, kh, vh):
        self._kv_write(i, kh, vh)
        ck, cv, *scales = self.caches
        return paged_attention(qh, ck, cv, self.tables, self.ctx, layer=i,
                               window=self.cfg.window, **self.paged_kw,
                               **dict(zip(("k_scale", "v_scale"), scales)))


class _PromptMix(_Mix):
    """A whole prompt of ONE request from position 0 (``pos`` is
    ``arange(P)``, the first ``n_valid`` rows real): causal attention
    within the span."""

    def __init__(self, cfg, caches, pos, blk, off, n_valid=None,
                 shardings=None):
        super().__init__(cfg, caches, pos, blk, off)
        self.n_valid, self.span_kw = n_valid, _head_shard_kw(shardings)

    def attend(self, i, qh, kh, vh):
        # attend to the rows as the cache holds them: every path must
        # see an int8 cache's round trip, or a later chunk / decode
        # step reading the cache would diverge from the hidden states
        # this very pass computed
        kh, vh = self._kv_write(i, kh, vh, read_back=True)
        return masked_attention(qh, kh, vh, 0,
                                score_scale(self.cfg.head_dim),
                                window=self.cfg.window,
                                n_valid=self.n_valid, **self.span_kw)


class _TableMix(_Mix):
    """Rows whose earlier positions' K/V already sits in the cache: a
    chunk of ONE request (``pos (C,)`` from ``start``, the first
    ``n_valid`` rows real, ``table (W,)``) or verify's ``k+1`` rows of
    each of B requests (``pos (B, k+1)``, ``start (B,)``, ``table (B,
    W)``).  The rows' K/V is written through the table FIRST and each
    row then attends to every cache position <= its own: decode's
    write-then-attend, exact in-span causality without a (C, C) mask."""

    def __init__(self, cfg, caches, pos, table, blk, off, start,
                 n_valid=None, shardings=None):
        super().__init__(cfg, caches, pos, blk, off)
        self.table, self.start, self.n_valid = table, start, n_valid
        self.S = table.shape[-1] * cfg.block_size
        self.span_kw = _head_shard_kw(shardings)

    def attend(self, i, qh, kh, vh):
        self._kv_write(i, kh, vh)
        ck, cv, *scales = self.caches
        # a request's rows share its table: ONE gather of its logical
        # view per layer (ck[i, table] gathers from the stack;
        # ck[i][table] would first copy the layer's whole pool)
        view = self.table.shape[:-1] + (self.S, self.cfg.kv_heads)
        kb = ck[i, self.table].reshape(view + (self.cfg.head_dim,))
        vb = cv[i, self.table].reshape(view + (self.cfg.head_dim,))
        if scales:
            ksc, vsc = scales
            kb = _kv_dequant(kb, ksc[i, self.table].reshape(view), qh.dtype)
            vb = _kv_dequant(vb, vsc[i, self.table].reshape(view), qh.dtype)
        return masked_attention(qh, kb, vb, self.start,
                                score_scale(self.cfg.head_dim),
                                window=self.cfg.window,
                                n_valid=self.n_valid, **self.span_kw)


def layer(cfg, params, i, x, mix, adp=None, slots=None):
    """THE layer of a gpt() decoder over rows ``x (..., D)``: norm ->
    q/k/v -> heads -> rotary (no position table) -> ``mix.attend`` ->
    proj -> mlp.  ``mix``: the pass's :class:`_Mix`; ``adp``/``slots``:
    the LoRA stacks and the rows' slots (None on adapters-off engines)."""
    Hq, Hkv, Dh = cfg.num_heads, cfg.kv_heads, cfg.head_dim
    p = f"{cfg.name}_l{i}"
    rows = x.shape[:-1]
    h = _ln(x, params[f"{p}_ln1_gamma"],
            None if cfg.rmsnorm else params[f"{p}_ln1_beta"])
    q = _awfc(cfg, params, adp, f"{p}_q", h, slots)
    k = _awfc(cfg, params, adp, f"{p}_k", h, slots)
    v = _awfc(cfg, params, adp, f"{p}_v", h, slots)
    qh = q.reshape(rows + (Hq, Dh))
    kh = k.reshape(rows + (Hkv, Dh))
    vh = v.reshape(rows + (Hkv, Dh))
    if cfg.pos_table is None:
        qh, kh = _rope(qh, mix.pos), _rope(kh, mix.pos)
    attn = mix.attend(i, qh, kh, vh)
    x = x + _awfc(cfg, params, adp, f"{p}_proj",
                  attn.reshape(rows + (Hq * Dh,)), slots)
    return x + _mlp(cfg, params, p, x, adp=adp, slots=slots)


def _stack(cfg, params, x, mix, adp=None, slots=None):
    for i in range(cfg.n_layers):
        x = layer(cfg, params, i, x, mix, adp, slots)
    return x


def _forward_token_batch(cfg, params, ck, cv, ksc, vsc, toks, pos, tables,
                         adp=None, slots=None, shardings=None):
    """Shared decode math: write each row's K/V at its position,
    attend through the block tables, return logits (B, V) and the
    caches (``ksc``/``vsc`` the scales of int8 caches, else None)."""
    x = _embed(cfg, params, toks, pos)
    mix = _DecodeMix(cfg, (ck, cv) if ksc is None else (ck, cv, ksc, vsc),
                     pos, tables, shardings)
    x = _stack(cfg, params, x, mix, adp, slots)
    return (_logits(cfg, params, x),) + (mix.caches + (None, None))[:4]


# -- one prologue, one epilogue ------------------------------------------------
def _operands(cfg, rest, n_host):
    """A program's operands after ``params``, in ``Engine.
    _program_specs``' order: [adapter stacks] caches (2, or 4 with
    int8-KV scales), ``n_host`` host-fed operands, [adapter slots],
    [temp, top_p, top_k], key.  Returns ``(adp, caches, host, slots,
    sampling)``, ``sampling`` the tail :func:`_finish` takes."""
    adp = slots = None
    if cfg.adapters:
        adp, rest = rest[0], rest[1:]
    n = 4 if cfg.kv_quant else 2
    caches, host, tail = rest[:n], rest[n:n + n_host], rest[n + n_host:]
    if cfg.adapters:
        slots, tail = tail[0], tail[1:]
    return adp, caches, host, slots, tail


def _outputs(cfg, lead, logits, caches):
    """A program's output tuple: the host-bound lead outputs, the
    watchdog flag, the caches."""
    if cfg.numeric_watch:
        # one extra all-reduce over the logits: the watchdog flag
        # rides back with the sampled tokens (the host syncs on
        # them anyway), so a NaN fires the flight recorder instead
        # of silently poisoning every later token
        lead += (jnp.isfinite(logits).all(),)
    return lead + tuple(caches)


def _finish(cfg, logits, caches, sampling, scalar):
    """Sample a token a row of ``logits``; the program's outputs (in
    sampling mode with the token's logprob views).  ``scalar``: ONE
    request, whose row's values are returned, not 1-row arrays."""
    if cfg.sampling:
        temp, topp, topk, rng = sampling
        tok = _sample_ops(cfg, logits, rng, temp, topp, topk)
        lead = (tok,) + _logprob_outs(logits, tok)
    else:
        rng, = sampling
        lead = (_sample(cfg, logits, rng),)
    if scalar:
        lead = tuple(o[0] for o in lead)
    return _outputs(cfg, lead, logits, caches)


def _jit_kwargs(cfg, donate, shardings, n_token_args, n_lead=None):
    """Shared jit options for the bucket programs.  With a tp mesh the
    in/out shardings are pinned explicitly — params per the partition
    rules, KV-cache head-sharded (scale arrays too, under int8 KV),
    everything host-fed replicated — so GSPMD partitions the program
    (inserting the two all-reduces per layer) instead of inferring a
    layout per call site.

    ``n_token_args`` counts the host-fed operands between the caches
    and the rng key AS THE GREEDY PROGRAM takes them; sampling-mode
    programs append the (temp, top_p, top_k) triple, counted here.
    ``n_lead`` is the host-bound output count ahead of the watchdog
    flag/caches (default: 1 sampled-token output, +3 logprob views in
    sampling mode)."""
    n_caches = 4 if cfg.kv_quant else 2
    if cfg.sampling:
        n_token_args += 3
    if cfg.adapters:
        n_token_args += 1            # the per-row adapter-slot operand
    if n_lead is None:
        n_lead = 4 if cfg.sampling else 1
    first = 2 if cfg.adapters else 1  # adp stacks sit after params
    kw = {"donate_argnums": (tuple(range(first, first + n_caches))
                             if donate else ())}
    if shardings is not None:
        rep = shardings.rep
        caches = (shardings.cache,) * 2
        if cfg.kv_quant:
            caches += (shardings.scale,) * 2
        lead_in = (shardings.params,)
        if cfg.adapters:
            lead_in += (shardings.adapters
                        if shardings.adapters is not None else rep,)
        kw["in_shardings"] = (lead_in + caches
                              + (rep,) * n_token_args + (rep,))
        out = (rep,) * n_lead
        if cfg.numeric_watch:
            out += (rep,)
        kw["out_shardings"] = out + caches
    return kw


def _build_decode(cfg, donate, shardings=None):
    def decode(params, *rest):
        adp, caches, (toks, pos, tables), slots, sampling = _operands(
            cfg, rest, 3)
        x = _embed(cfg, params, toks, pos)                 # (B, D)
        mix = _DecodeMix(cfg, caches, pos, tables, shardings)
        x = _stack(cfg, params, x, mix, adp, slots)
        return _finish(cfg, _logits(cfg, params, x), mix.caches, sampling,
                       scalar=False)

    return jax.jit(decode, **_jit_kwargs(cfg, donate, shardings, 3))


def _build_prefill(cfg, P, donate, shardings=None):
    def prefill(params, *rest):
        """Whole-prompt pass at padded length P for ONE request:
        writes K/V for positions [0, plen) through the block
        table and samples the token after position plen-1."""
        adp, caches, (toks, plen, blk, off), slots, sampling = _operands(
            cfg, rest, 4)
        pos = jnp.arange(P)
        x = _embed(cfg, params, toks, slice(None, P))      # (P, D)
        mix = _PromptMix(cfg, caches, pos, blk, off, plen, shardings)
        x = _stack(cfg, params, x, mix, adp, slots)
        return _finish(cfg, _logits(cfg, params, x[plen - 1][None]),
                       mix.caches, sampling, scalar=True)

    return jax.jit(prefill, **_jit_kwargs(cfg, donate, shardings, 4))


def _build_restore(cfg, donate, shardings=None):
    """Host-tier restore program: scatter R parked blocks' host copies
    back into the device cache through their (freshly allocated) block
    ids.  Pure data movement — no params, no sampling: the caches are
    donated through so the copy is in-place, padding rows write zeros
    into the null block (contents garbage by design), and under tp the
    replicated host operands scatter onto the head-sharded cache."""
    n_caches = 4 if cfg.kv_quant else 2

    def restore(*args):
        """The caches, the block ids, then one host copy a cache."""
        blks = args[n_caches]
        return tuple(c.at[:, blks].set(h)
                     for c, h in zip(args[:n_caches], args[n_caches + 1:]))

    kw = {"donate_argnums": (tuple(range(n_caches)) if donate else ())}
    if shardings is not None:
        caches = (shardings.cache,) * 2
        if cfg.kv_quant:
            caches += (shardings.scale,) * 2
        kw["in_shardings"] = caches + (shardings.rep,) * (n_caches + 1)
        kw["out_shardings"] = caches
    return jax.jit(restore, **kw)


# -- the token pool: a sampled token from one pass to the next, on device ----
# ``Engine`` enqueues pass t+1 before it reads pass t's tokens, so a row's
# input token is still on the device.  Three small programs around the
# bucket programs, which stay as they are: a decode pass's tokens go to
# the head of a small int32 pool, a prefill pass's token to a row behind
# it, and the next decode's token operand takes each row from the pool
# (``src`` its row there) or from the host (``src`` -1: the host has read
# that token already).  A decode pass whose tokens are all on the host
# gets them as before, so ONE decode program serves both.
TOK_TAKE, TOK_PUT, TOK_PUT1 = "tok_take", "tok_put", "tok_put1"


def _build_token_program(kind, shardings=None):
    """``TOK_TAKE`` ``(pool, toks, src) -> toks``, ``TOK_PUT`` ``(pool,
    out) -> pool`` (a decode bucket's tokens to rows ``[0, bucket)``),
    ``TOK_PUT1`` ``(pool, tok, at) -> pool`` (one prefill's token)."""
    def tok_take(pool, toks, src):
        return jnp.where(src >= 0, pool[jnp.maximum(src, 0)], toks)

    def tok_put(pool, out):
        return jax.lax.dynamic_update_slice(pool, out, (0,))

    def tok_put1(pool, tok, at):
        return pool.at[at].set(tok)

    fn = {TOK_TAKE: tok_take, TOK_PUT: tok_put, TOK_PUT1: tok_put1}[kind]
    kw = {}
    if shardings is not None:
        kw = {"in_shardings": (shardings.rep,) * (2 if kind == TOK_PUT
                                                  else 3),
              "out_shardings": shardings.rep}
    return jax.jit(fn, **kw)


def _build_chunk(cfg, C, donate, shardings=None):
    """Suffix/chunk prefill program: C token rows of ONE request whose
    earlier positions' K/V already sit in the cache (a prefix-cache hit
    or previous chunks of the same prompt); see :class:`_TableMix`."""

    def chunk(params, *rest):
        """Rows hold positions [start, start+n_valid) (rows past
        n_valid are padding: they write into the null block and their
        outputs are discarded).  Samples the token after position
        start+n_valid-1 — meaningful on the final chunk only."""
        (adp, caches, (toks, start, n_valid, table, blk, off), slots,
         sampling) = _operands(cfg, rest, 6)
        pos = start + jnp.arange(C)
        x = _embed(cfg, params, toks, pos, clamp=True)     # (C, D)
        mix = _TableMix(cfg, caches, pos, table, blk, off, start, n_valid,
                        shardings)
        x = _stack(cfg, params, x, mix, adp, slots)
        return _finish(cfg, _logits(cfg, params, x[n_valid - 1][None]),
                       mix.caches, sampling, scalar=True)

    return jax.jit(chunk, **_jit_kwargs(cfg, donate, shardings, 6))
