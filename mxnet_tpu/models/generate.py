"""Incremental (KV-cache) decoding for the GPT model family.

Training runs the full-sequence graph (models/transformer.py); serving
wants O(1) work per generated token.  This module rebuilds the decoder
as a single-token step over cached keys/values and runs the WHOLE
generation loop as one ``lax.scan`` inside one jit — prompt prefill and
sampling included — so a generate call is one XLA program dispatch with
the cache resident in HBM (the TPU-idiomatic shape for autoregressive
serving; contrast the reference's per-step executor calls in
example/rnn char-rnn style inference).

Operates directly on a trained parameter dict (``Module.get_params()``
/ ``FeedForward`` checkpoints / ``ShardedTrainer.get_params()``), with
a parity test against the training graph in
``tests/test_generate.py``.
"""

from __future__ import annotations

import warnings

import numpy as np

import jax
import jax.numpy as jnp

from ..ops.attention import score_scale

__all__ = ["gpt_generate", "gpt_decode_config", "normalize_gpt_params",
           "detect_gpt_variant", "reconcile_decode_config"]

_decoder_cache = {}


def _ln(x, gamma, beta, eps=1e-5):
    xf = x.astype(jnp.float32)
    if beta is None:          # rmsnorm checkpoint: no shift, no centering
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        return (xf * jax.lax.rsqrt(ms + eps)
                * gamma.astype(jnp.float32)).astype(x.dtype)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    return (y * gamma.astype(jnp.float32)
            + beta.astype(jnp.float32)).astype(x.dtype)


def _fc(x, w, b):
    return x @ w.T.astype(x.dtype) + b.astype(x.dtype)


def _gelu(x):
    xf = x.astype(jnp.float32)
    # np.float32: a NumPy float64 scalar would promote the erf to f64
    return (0.5 * xf * (1.0 + jax.lax.erf(xf / np.float32(np.sqrt(2.0))))
            ).astype(x.dtype)


def gpt_decode_config(symbol):
    """Decode-time config a :func:`mxnet_tpu.models.gpt` symbol carries
    that is NOT recoverable from weight shapes: ``num_heads`` and the
    trained sliding-window radius (``attn_window``).  Works on a freshly
    built symbol or one round-tripped through the two-artifact
    checkpoint (``model.load_checkpoint``), since node attrs serialize.
    Returns ``{"num_heads": int, "window": int}``; raises if the symbol
    carries no gpt config attrs (predates them, or not a gpt symbol)."""
    heads = symbol.attr("__gpt_num_heads__")
    if heads is None:
        raise ValueError(
            "symbol carries no __gpt_num_heads__ attr — not built by "
            "models.gpt(), or saved before decode-config persistence; "
            "pass num_heads/window to gpt_generate explicitly")
    return {"num_heads": int(heads),
            "window": int(symbol.attr("__gpt_attn_window__") or 0)}


def reconcile_decode_config(symbol, num_heads, window):
    """Merge explicit ``num_heads``/``window`` overrides with the
    symbol's persisted decode config (:func:`gpt_decode_config`),
    raising on contradiction — the reshapes would succeed either way
    and silently decode garbage.  Shared by :func:`gpt_generate` and
    ``serve.Engine`` so the two decoders cannot drift.  Returns the
    resolved ``(num_heads, window)``."""
    cfg = gpt_decode_config(symbol)
    if num_heads is None:
        num_heads = cfg["num_heads"]
    elif int(num_heads) != cfg["num_heads"]:
        raise ValueError(
            f"num_heads={num_heads} contradicts the symbol's "
            f"num_heads={cfg['num_heads']} — the reshapes would "
            "succeed and decode garbage")
    if window is None:
        window = cfg["window"]
    elif int(window) != cfg["window"]:
        raise ValueError(
            f"window={window} contradicts the symbol's trained "
            f"attn_window={cfg['window']} — decoding with a "
            "different window silently changes the model")
    return num_heads, window


def normalize_gpt_params(params, name="gpt"):
    """Canonicalize a gpt() checkpoint for decoding: dequantize
    weight-only-int8 entries (``*_wscale``) and split ``fused_qkv``
    projections back into the per-tensor ``*_{q,k,v}_*`` layout every
    decoder (generate.py's scan loop, serve.Engine's paged steps)
    addresses.  Returns the input dict unchanged when neither applies.
    """
    try:
        tok_w = params[f"{name}_tok_embed_weight"]
    except KeyError:
        raise ValueError(
            f"params has no '{name}_tok_embed_weight' — wrong name "
            "prefix or not a gpt() parameter dict") from None
    d_model = tok_w.shape[1]
    if any(k.endswith("_wscale") for k in params):
        # quantized checkpoint (contrib/quantization.py): dequantize the
        # int8 weights once at load — decode then runs the normal path
        # (weight-only int8 semantics)
        params = dict(params)
        for k in [k for k in params if k.endswith("_wscale")]:
            stem = k[: -len("_wscale")]
            wq = np.asarray(params[stem + "_weight"], np.float32)
            scale = np.asarray(params.pop(k), np.float32)
            params[stem + "_weight"] = wq * scale[:, None]
    if f"{name}_l0_qkv_weight" in params:
        # fused_qkv=True checkpoint layout: split each projection back
        # into the q/k/v entries the decoder addresses.  GQA fused
        # checkpoints emit (d_model + 2*d_kv) rows, so split at the
        # boundaries rather than in thirds.
        params = dict(params)
        rows = np.asarray(params[f"{name}_l0_qkv_weight"]).shape[0]
        d_kv_f = (rows - d_model) // 2
        i = 0
        while f"{name}_l{i}_qkv_weight" in params:
            for kind in ("weight", "bias"):
                whole = np.asarray(params.pop(f"{name}_l{i}_qkv_{kind}"))
                parts = np.split(whole, [d_model, d_model + d_kv_f],
                                 axis=0)
                for x, part in zip(("q", "k", "v"), parts):
                    params[f"{name}_l{i}_{x}_{kind}"] = part
            i += 1
    return params


def detect_gpt_variant(params, num_heads, name="gpt"):
    """Model-variant flags recoverable from a NORMALIZED checkpoint
    (see :func:`normalize_gpt_params`): layer count, head-dim split,
    grouped-query kv_heads, rope-vs-learned positions (``pos_table`` is
    the table length, None for rope), SwiGLU MLP, tied LM head, and
    rmsnorm.  ``num_heads`` itself is NOT recoverable from shapes —
    callers read it from the symbol (gpt_decode_config) or take it
    explicitly."""
    tok_w = params[f"{name}_tok_embed_weight"]
    d_model = tok_w.shape[1]
    pos_w = params.get(f"{name}_pos_embed_weight")
    n_layers = 0
    while f"{name}_l{n_layers}_q_weight" in params:
        n_layers += 1
    if n_layers == 0:
        raise ValueError(f"no '{name}_l0_q_weight' (or '_l0_qkv_weight') "
                         f"in params — wrong name prefix or not a gpt() "
                         "parameter dict")
    if d_model % num_heads:
        raise ValueError("num_heads must divide d_model")
    head_dim = d_model // num_heads
    return {
        "n_layers": n_layers,
        "d_model": d_model,
        "head_dim": head_dim,
        "kv_heads": (np.asarray(params[f"{name}_l0_k_weight"]).shape[0]
                     // head_dim),
        "vocab": tok_w.shape[0],
        "pos_table": None if pos_w is None else pos_w.shape[1],
        "swiglu": f"{name}_l0_ff_gate_weight" in params,
        "tied": f"{name}_head_weight" not in params,
        "rmsnorm": f"{name}_l0_ln1_beta" not in params,
    }


def gpt_generate(params, prompt, max_new_tokens, num_heads=None,
                 temperature=0.0, top_k=None, key=None, window=None,
                 name="gpt", symbol=None):
    """Generate continuations for ``prompt`` with a KV cache.

    Args:
      params: dict name->array of trained GPT weights (numpy or jax),
        with the naming of :func:`mxnet_tpu.models.gpt`.
      prompt: int array (batch, prompt_len) of token ids.
      max_new_tokens: tokens to append after the prompt.
      num_heads: attention head count the model was built with (not
        recoverable from weight shapes).
      temperature: 0.0 -> greedy argmax; otherwise sample from
        softmax(logits / temperature).
      top_k: optionally restrict sampling to the k most likely tokens.
      key: jax PRNG key for sampling (defaults to PRNGKey(0)).
      window: sliding-window radius the model was TRAINED with
        (models.gpt attn_window); 0 = full attention.
      name: the symbol-name prefix used when building the model.

    Model variants are detected from the checkpoint itself: the K
    projection's row count gives kv_heads (grouped-query attention), a
    missing position table means rope, an ``*_ff_gate_weight`` means a
    SwiGLU MLP, and a missing ``*_head_weight`` means the LM head is
    the tied token-embedding matrix.

    Returns ``(batch, prompt_len + max_new_tokens)`` numpy int32 ids
    (prompt included).  The compiled decode loop is cached per
    (config, shapes) so repeated calls don't re-trace.
    """
    prompt = np.asarray(prompt)
    if prompt.ndim != 2:
        raise ValueError("prompt must be (batch, prompt_len)")
    if symbol is not None:
        num_heads, window = reconcile_decode_config(symbol, num_heads,
                                                    window)
    if num_heads is None:
        raise ValueError("num_heads is required (pass it, or pass "
                         "symbol= to read it from the trained graph)")
    if window is None:
        # not auto-detectable from weights alone: a window-trained
        # checkpoint decoded without window= would silently run full
        # attention.  Explicit window=0 (or symbol=) silences this.
        warnings.warn(
            "gpt_generate: window not given and no symbol= to detect it "
            "from; assuming full attention (window=0). If the model was "
            "trained with attn_window>0 this is a silent mismatch — "
            "pass window= or symbol=.", stacklevel=2)
        window = 0
    if window < 0:
        raise ValueError(f"window must be >= 0 (got {window})")
    B, P = prompt.shape
    if P < 1:
        raise ValueError("prompt must hold at least one token")

    params = normalize_gpt_params(params, name)
    spec = detect_gpt_variant(params, num_heads, name)
    tok_w = params[f"{name}_tok_embed_weight"]
    n_layers, head_dim = spec["n_layers"], spec["head_dim"]
    kv_heads = spec["kv_heads"]
    swiglu, tied, rmsnorm = spec["swiglu"], spec["tied"], spec["rmsnorm"]
    # pos_embed="rope" checkpoints carry no position table; positions
    # then have no trained length limit, so the cache sizes to the
    # request instead of the table
    S = spec["pos_table"]
    T = P + max_new_tokens
    if S is not None and T > S:
        raise ValueError(
            f"prompt_len + max_new_tokens = {T} exceeds the model's "
            f"positional table ({S})")
    S_cache = T if S is None else S

    if max_new_tokens < 1:
        return np.asarray(prompt, np.int32)

    cfg = (name, n_layers, num_heads, head_dim, B, P, max_new_tokens,
           S_cache, float(temperature), top_k, kv_heads, S is None,
           int(window), swiglu, tied, rmsnorm,
           str(jnp.asarray(tok_w).dtype))
    run = _decoder_cache.get(cfg)
    if run is None:
        run = _build_decoder(name, n_layers, num_heads, head_dim, B, P,
                             max_new_tokens, S_cache, float(temperature),
                             top_k, kv_heads=kv_heads, rope=S is None,
                             window=int(window), swiglu=swiglu, tied=tied,
                             rmsnorm=rmsnorm)
        _decoder_cache[cfg] = run

    if key is None:
        key = jax.random.PRNGKey(0)
    jparams = {k: jnp.asarray(v) for k, v in params.items()}
    ids = run(jparams, jnp.asarray(prompt, jnp.int32), key)
    return np.asarray(jax.device_get(ids), np.int32)


def _build_decoder(name, n_layers, num_heads, head_dim, B, P,
                   max_new_tokens, S, temperature, top_k, kv_heads=None,
                   rope=False, window=0, swiglu=False, tied=False,
                   rmsnorm=False):
    d_model = num_heads * head_dim
    T = P + max_new_tokens
    kv_heads = kv_heads or num_heads
    group = num_heads // kv_heads
    half = head_dim // 2

    def _rot(u, t):
        """RoPE rotation of (B, H, Dh) at scalar position t (matches
        ops/attention.py RoPEOp with offset folded into t)."""
        inv = 10000.0 ** (-jnp.arange(half, dtype=jnp.float32) / half)
        ang = t.astype(jnp.float32) * inv                     # (half,)
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        uf = u.astype(jnp.float32)
        u1, u2 = uf[..., :half], uf[..., half:]
        return jnp.concatenate([u1 * cos - u2 * sin,
                                u1 * sin + u2 * cos],
                               axis=-1).astype(u.dtype)

    def step_token(params, tok, t, cache_k, cache_v):
        """One decode position: tok (B,) int32 at position t; caches
        (L, B, Hkv, S, Dh).  Returns logits (B, V) + updated caches."""
        x = params[f"{name}_tok_embed_weight"][tok]            # (B, D)
        if not rope:
            x = x + params[f"{name}_pos_embed_weight"][0, t]
        pos_mask = (jnp.arange(S) <= t)                        # (S,)
        if window:
            pos_mask = jnp.logical_and(pos_mask,
                                       jnp.arange(S) > t - window)
        for i in range(n_layers):
            p = f"{name}_l{i}"
            h = _ln(x, params[f"{p}_ln1_gamma"],
                    None if rmsnorm else params[f"{p}_ln1_beta"])
            q = _fc(h, params[f"{p}_q_weight"], params[f"{p}_q_bias"])
            k = _fc(h, params[f"{p}_k_weight"], params[f"{p}_k_bias"])
            v = _fc(h, params[f"{p}_v_weight"], params[f"{p}_v_bias"])
            qh = q.reshape(B, num_heads, head_dim)
            kh = k.reshape(B, kv_heads, head_dim)
            vh = v.reshape(B, kv_heads, head_dim)
            if rope:
                qh, kh = _rot(qh, t), _rot(kh, t)
            # write this token's k/v at position t, then attend over <=t
            cache_k = cache_k.at[i, :, :, t, :].set(kh)
            cache_v = cache_v.at[i, :, :, t, :].set(vh)
            # grouped-query: kv head g serves q heads [g*group, ...)
            qg = qh.reshape(B, kv_heads, group, head_dim)
            scores = jnp.einsum("bkgd,bksd->bkgs", qg, cache_k[i])
            scores = scores * score_scale(head_dim)
            scores = jnp.where(pos_mask[None, None, None, :], scores,
                               -jnp.inf)
            probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
            attn = jnp.einsum("bkgs,bksd->bkgd", probs.astype(x.dtype),
                              cache_v[i])
            x = x + _fc(attn.reshape(B, d_model),
                        params[f"{p}_proj_weight"], params[f"{p}_proj_bias"])
            h2 = _ln(x, params[f"{p}_ln2_gamma"],
                     None if rmsnorm else params[f"{p}_ln2_beta"])
            if swiglu:
                g = _fc(h2, params[f"{p}_ff_gate_weight"],
                        params[f"{p}_ff_gate_bias"])
                gf = g.astype(jnp.float32)       # f32 silu == sym.silu
                up = ((gf * jax.nn.sigmoid(gf)).astype(g.dtype)
                      * _fc(h2, params[f"{p}_ff_up_weight"],
                            params[f"{p}_ff_up_bias"]))
            else:
                up = _gelu(_fc(h2, params[f"{p}_ff_up_weight"],
                               params[f"{p}_ff_up_bias"]))
            x = x + _fc(up, params[f"{p}_ff_down_weight"],
                        params[f"{p}_ff_down_bias"])
        final = _ln(x, params[f"{name}_ln_f_gamma"],
                    None if rmsnorm else params[f"{name}_ln_f_beta"])
        if tied:
            # tied checkpoint: the LM head is the embedding matrix
            logits = final @ params[f"{name}_tok_embed_weight"].T.astype(
                final.dtype)
        else:
            logits = _fc(final, params[f"{name}_head_weight"],
                         params[f"{name}_head_bias"])
        return logits, cache_k, cache_v

    def sample(logits, key):
        if temperature == 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        logits = logits.astype(jnp.float32) / temperature
        if top_k is not None:
            kth = jnp.sort(logits, axis=-1)[:, -int(top_k)][:, None]
            logits = jnp.where(logits >= kth, logits, -jnp.inf)
        return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)

    def run(params, prompt, key):
        cache_k = jnp.zeros((n_layers, B, kv_heads, S, head_dim),
                            params[f"{name}_tok_embed_weight"].dtype)
        cache_v = jnp.zeros_like(cache_k)
        # tokens fed at each step: prompt for t < P, then sampled
        prompt_t = jnp.transpose(prompt)                      # (P, B)

        def body(carry, t):
            cache_k, cache_v, next_tok, key = carry
            tok = jnp.where(t < P,
                            prompt_t[jnp.minimum(t, P - 1)], next_tok)
            logits, cache_k, cache_v = step_token(params, tok, t,
                                                  cache_k, cache_v)
            key, sub = jax.random.split(key)
            sampled = sample(logits, sub)
            return (cache_k, cache_v, sampled, key), (tok, sampled)

        init = (cache_k, cache_v, jnp.zeros((B,), jnp.int32), key)
        _, (fed, sampled) = jax.lax.scan(body, init, jnp.arange(T - 1))
        # position t's sample is the token for position t+1; the ids
        # actually consumed are fed[0:T-1] plus the final sample
        ids = jnp.concatenate([fed, sampled[-1:]], axis=0)    # (T, B)
        return jnp.transpose(ids)

    return jax.jit(run)
