"""Hybrid decoder: state-space (Mamba-2) and attention layers in one stack.

A decoder-only language model whose layers are of two kinds, chosen per
layer by ``layer_types``: a Mamba-2 mixer (causal depthwise convolution,
selective state-space scan, gated RMSNorm) or grouped-query causal
attention WITHOUT positional encoding and with an explicit score scale.
Every layer is followed by one dense SwiGLU block; four scalar
multipliers scale the embedding, every residual branch, the attention
scores and the logits; the head is tied to the embedding.

    h_0 = emb_mult * E[token]
    h   = h + res_mult * Mixer_l(RMSNorm(h))
    h   = h + res_mult * W_out(silu(g) * u),   [g, u] = W_in RMSNorm(h)
    logits = RMSNorm(h_L) E^T / logits_scaling

Mamba-2 mixer (H heads of size P, one group, state size N, kernel K):

    [z | xBC | dt] = W_in x
    xBC_t = silu(b + sum_j w_j * xBC_{t-K+1+j})       (depthwise, causal)
    dt_t  = softplus(dt_t + dt_bias);  a_t = exp(-exp(A_log) * dt_t)
    S_t   = a_t S_{t-1} + dt_t * x_t (x) B_t;   y_t = S_t C_t + D * x_t
    out   = W_out RMSNorm_w(y_t * silu(z_t))

This file holds the DESCRIPTION (:func:`hybrid_decoder`), seeded
parameters made on the device (:func:`init_params`) and the plain
reference (:func:`reference_logits`: float32, ``highest`` matmul
precision, the recurrence token by token, no cache, no chunking).  It
is served by ``mx.serve.Engine(params, symbol=hybrid_decoder(...))``
through ``serve/hybrid.py``.  Training this model through the Symbol
graph is out of scope: there is no Symbol here, and the descriptor has
no ``bind``.

Parameter names (a flat dict, ``FullyConnected`` layout ``(out, in)``):
``{name}_tok_embed_weight (V, D)``, ``{name}_ln_f_gamma``; per layer
``{name}_l{i}_ln1_gamma``, ``_ln2_gamma``, ``_ff_in_weight (2 d_ff,
D)`` (gate rows first), ``_ff_out_weight (D, d_ff)``; attention layers
``_qkv_weight ((Hq + 2 Hkv) Dh, D)``, ``_proj_weight (D, Hq Dh)``;
Mamba layers ``_in_proj_weight (2 d_inner + 2 N + H, D)``,
``_conv_weight (d_inner + 2 N, K)``, ``_conv_bias``, ``_dt_bias (H)``,
``_A_log (H)``, ``_D (H)``, ``_norm_gamma (d_inner)``,
``_out_proj_weight (D, d_inner)``.
"""

from __future__ import annotations

import collections

import numpy as np

import jax
import jax.numpy as jnp

__all__ = ["hybrid_decoder", "HybridDecoder", "init_params",
           "reference_logits"]

_FIELDS = ["vocab_size", "d_model", "layer_types", "num_heads", "kv_heads",
           "head_dim", "d_ff", "mamba_heads", "mamba_head_dim",
           "mamba_state", "mamba_conv", "mamba_chunk", "eps",
           "embedding_multiplier", "residual_multiplier",
           "attention_multiplier", "logits_scaling", "name"]


class HybridDecoder(collections.namedtuple("HybridDecoder", _FIELDS)):
    """The static description of a hybrid decoder (hashable: the serving
    programs close over it).  ``layer_types`` is a tuple of ``"mamba"``
    / ``"attention"``, one per layer."""

    __slots__ = ()

    # what serve/hybrid.py and the engine read of ANY description: the head
    # is the embedding, every layer's feed-forward is dense, no layer has a
    # window, a position's K/V heads lie side by side in the cache, and
    # the state-space heads share one group's B and C
    tied = True
    window_layers = ()
    kv_flat = True
    diffusion = False           # one token a pass
    mamba_groups = 1

    @property
    def ffn_types(self):
        return ("dense",) * len(self.layer_types)

    @property
    def global_layers(self):
        """The layers whose K/V live in the one whole-context cache."""
        return self.attention_layers

    @property
    def num_layers(self):
        return len(self.layer_types)

    @property
    def d_inner(self):
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_dim(self):
        """Width of the convolution's input: x, B and C of one group."""
        return self.d_inner + 2 * self.mamba_state

    @property
    def attention_layers(self):
        return tuple(i for i, t in enumerate(self.layer_types)
                     if t == "attention")

    @property
    def mamba_layers(self):
        return tuple(i for i, t in enumerate(self.layer_types)
                     if t == "mamba")

    def param_shapes(self):
        """name -> shape of every parameter, in layer order."""
        n, D = self.name, self.d_model
        out = {f"{n}_tok_embed_weight": (self.vocab_size, D)}
        qkv = (self.num_heads + 2 * self.kv_heads) * self.head_dim
        for i, kind in enumerate(self.layer_types):
            p = f"{n}_l{i}"
            out[f"{p}_ln1_gamma"] = (D,)
            if kind == "attention":
                out[f"{p}_qkv_weight"] = (qkv, D)
                out[f"{p}_proj_weight"] = (D, self.num_heads * self.head_dim)
            else:
                out[f"{p}_in_proj_weight"] = (
                    self.d_inner + self.conv_dim + self.mamba_heads, D)
                out[f"{p}_conv_weight"] = (self.conv_dim, self.mamba_conv)
                out[f"{p}_conv_bias"] = (self.conv_dim,)
                out[f"{p}_dt_bias"] = (self.mamba_heads,)
                out[f"{p}_A_log"] = (self.mamba_heads,)
                out[f"{p}_D"] = (self.mamba_heads,)
                out[f"{p}_norm_gamma"] = (self.d_inner,)
                out[f"{p}_out_proj_weight"] = (D, self.d_inner)
            out[f"{p}_ln2_gamma"] = (D,)
            out[f"{p}_ff_in_weight"] = (2 * self.d_ff, D)
            out[f"{p}_ff_out_weight"] = (D, self.d_ff)
        out[f"{n}_ln_f_gamma"] = (D,)
        return out

    def num_params(self):
        return sum(int(np.prod(s)) for s in self.param_shapes().values())

    def init_params(self, seed, dtype="float32"):
        return init_params(self, seed, dtype)

    def reference_logits(self, params, tokens):
        return reference_logits(self, params, tokens)


def hybrid_decoder(vocab_size, d_model, layer_types, num_heads, kv_heads,
                   d_ff, mamba_heads, mamba_head_dim, mamba_state,
                   head_dim=None, mamba_conv=4, mamba_chunk=256, eps=1e-5,
                   embedding_multiplier=1.0, residual_multiplier=1.0,
                   attention_multiplier=None, logits_scaling=1.0,
                   name="hybrid"):
    """Describe a hybrid decoder for ``serve.Engine(params, symbol=...)``.

    ``layer_types``: one of ``"mamba"`` / ``"attention"`` per layer.
    ``attention_multiplier``: the score scale (default ``1/sqrt(head
    size)``).  One state-space group (B and C are shared by the heads).
    """
    layer_types = tuple(str(t) for t in layer_types)
    bad = sorted(set(layer_types) - {"mamba", "attention"})
    if bad or not layer_types:
        raise ValueError(f"hybrid_decoder: layer_types must name 'mamba' "
                         f"or 'attention' per layer (got {bad or 'none'})")
    if num_heads % kv_heads:
        raise ValueError(f"hybrid_decoder: num_heads={num_heads} is not a "
                         f"multiple of kv_heads={kv_heads}")
    if head_dim is None:
        head_dim = d_model // num_heads
    if attention_multiplier is None:
        attention_multiplier = 1.0 / float(np.sqrt(head_dim))
    return HybridDecoder(
        int(vocab_size), int(d_model), layer_types, int(num_heads),
        int(kv_heads), int(head_dim), int(d_ff), int(mamba_heads),
        int(mamba_head_dim), int(mamba_state), int(mamba_conv),
        int(mamba_chunk), float(eps), float(embedding_multiplier),
        float(residual_multiplier), float(attention_multiplier),
        float(logits_scaling), str(name))


def init_params(dec, seed, dtype="float32"):
    """Random parameters made on the device from the seed in ONE jitted
    call, in the dtype they are served in.  Matrices are N(0, 1/fan_in);
    the tied embedding N(0, logits_scaling^2 / d_model), so that the
    logits ``RMSNorm(h) E^T / logits_scaling`` have unit spread; norm
    gains 1, but the FINAL norm's gain random +-1 (with +1 everywhere a
    tied head under an embedding multiplier makes every position's own
    token win by tens of deviations); the convolution N(0, 1/K) with bias N(0, 0.01); and, as the
    Mamba-2 reference initialises them, ``A_log = log U(1, 16)``,
    ``dt_bias`` the inverse softplus of ``U(0.001, 0.1)`` and ``D = 1``
    (so that the decay ``a_t`` is neither 0 nor 1).  The three per-head
    vectors stay float32 whatever ``dtype`` is.  The seed enters as
    data: every seed runs the same compiled program."""
    shapes = dec.param_shapes()
    dtype = jnp.dtype(dtype)
    emb_std = dec.logits_scaling / float(np.sqrt(dec.d_model))

    def make(key):
        out = {}
        for i, (name, shape) in enumerate(shapes.items()):
            k = jax.random.fold_in(key, i)
            if name.endswith("ln_f_gamma"):
                # random signs: with gain 1 the tied head reads h_0 =
                # emb_mult * E[token] back through E^T, the last token's
                # own logit stands tens of deviations above the rest, and
                # greedy decoding repeats one token for ever: a check on
                # such a model can catch nothing
                sign = jax.random.bernoulli(k, 0.5, shape)
                out[name] = jnp.where(sign, 1.0, -1.0).astype(dtype)
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
                b = jax.random.normal(k, shape, jnp.float32)
                out[name] = (b * np.float32(0.1)).astype(dtype)
            elif name.endswith("tok_embed_weight"):
                w = jax.random.normal(k, shape, jnp.float32)
                out[name] = (w * np.float32(emb_std)).astype(dtype)
            else:
                w = jax.random.normal(k, shape, jnp.float32)
                out[name] = (w * np.float32(shape[-1] ** -0.5)
                             ).astype(dtype)
        return out

    # hardware bit generator: billions of values by threefry take long
    key = jax.random.key(int(seed) % (2 ** 31), impl="unsafe_rbg")
    key = jax.random.fold_in(key, int(seed) >> 31)
    return jax.jit(make)(key)


# -- the plain reference ---------------------------------------------------------

def _rms(x, gamma, eps):
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + np.float32(eps)) * gamma


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _ref_attention(dec, P, p, h):
    T = h.shape[0]
    Hq, Hkv, Dh = dec.num_heads, dec.kv_heads, dec.head_dim
    qkv = h @ P[f"{p}_qkv_weight"].T
    q = qkv[:, :Hq * Dh].reshape(T, Hkv, Hq // Hkv, Dh)
    k = qkv[:, Hq * Dh:(Hq + Hkv) * Dh].reshape(T, Hkv, Dh)
    v = qkv[:, (Hq + Hkv) * Dh:].reshape(T, Hkv, Dh)
    s = jnp.einsum("qkgd,skd->kgqs", q, k) * np.float32(
        dec.attention_multiplier)
    keep = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    s = jnp.where(keep[None, None], s, -jnp.inf)
    a = jnp.einsum("kgqs,skd->qkgd", jax.nn.softmax(s, axis=-1), v)
    return a.reshape(T, Hq * Dh) @ P[f"{p}_proj_weight"].T


def _ref_mamba(dec, P, p, h):
    """A Mamba-2 mixer of ``dec.mamba_groups`` state groups (head ``h``
    reads the ``B`` and ``C`` rows of group ``h // (H / G)`` and the gated
    norm takes its RMS over each group's channels; one group: all)."""
    T = h.shape[0]
    H, Pd, N, K, G = (dec.mamba_heads, dec.mamba_head_dim, dec.mamba_state,
                      dec.mamba_conv, dec.mamba_groups)
    di, cd = dec.d_inner, dec.conv_dim
    zxd = h @ P[f"{p}_in_proj_weight"].T
    z, xBC, dt = zxd[:, :di], zxd[:, di:di + cd], zxd[:, di + cd:]
    pad = jnp.concatenate([jnp.zeros((K - 1, cd), xBC.dtype), xBC], axis=0)
    w = P[f"{p}_conv_weight"]
    conv = P[f"{p}_conv_bias"][None, :]
    for j in range(K):
        conv = conv + pad[j:j + T] * w[None, :, j]
    xBC = _silu(conv)
    x = xBC[:, :di].reshape(T, H, Pd)
    # every head its own group's rows, (T, H, N)
    Bm = jnp.repeat(xBC[:, di:di + G * N].reshape(T, G, N), H // G, axis=1)
    Cm = jnp.repeat(xBC[:, di + G * N:].reshape(T, G, N), H // G, axis=1)
    dt = jax.nn.softplus(dt + P[f"{p}_dt_bias"][None, :])         # (T, H)
    a = jnp.exp(-jnp.exp(P[f"{p}_A_log"])[None, :] * dt)
    D = P[f"{p}_D"]

    def step(S, inp):
        x_t, B_t, C_t, dt_t, a_t = inp
        S = (a_t[:, None, None] * S
             + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :])
        y = jnp.sum(S * C_t[:, None, :], axis=-1) + D[:, None] * x_t
        return S, y

    # the recurrence as written, one position at a time
    _, y = jax.lax.scan(step, jnp.zeros((H, Pd, N), jnp.float32),
                        (x, Bm, Cm, dt, a))
    y = (y.reshape(T, di) * _silu(z)).reshape(T, G, di // G)
    y = _rms(y, P[f"{p}_norm_gamma"].reshape(G, di // G), dec.eps)
    return y.reshape(T, di) @ P[f"{p}_out_proj_weight"].T


def reference_logits(dec, params, tokens):
    """Logits (T, V) of one sequence's full forward pass: the equations
    of this module's docstring in float32 under
    ``jax.default_matmul_precision("highest")``, the recurrence token
    by token, no cache, no chunking, no kernels."""
    toks = jnp.asarray(np.asarray(tokens), jnp.int32)
    P = {k: jnp.asarray(v).astype(jnp.float32) for k, v in params.items()}
    n = dec.name
    with jax.default_matmul_precision("highest"):
        h = np.float32(dec.embedding_multiplier) \
            * P[f"{n}_tok_embed_weight"][toks]
        rm = np.float32(dec.residual_multiplier)
        for i, kind in enumerate(dec.layer_types):
            p = f"{n}_l{i}"
            u = _rms(h, P[f"{p}_ln1_gamma"], dec.eps)
            mix = _ref_attention if kind == "attention" else _ref_mamba
            h = h + rm * mix(dec, P, p, u)
            u = _rms(h, P[f"{p}_ln2_gamma"], dec.eps)
            gu = u @ P[f"{p}_ff_in_weight"].T
            g, up = gu[:, :dec.d_ff], gu[:, dec.d_ff:]
            h = h + rm * ((_silu(g) * up) @ P[f"{p}_ff_out_weight"].T)
        h = _rms(h, P[f"{n}_ln_f_gamma"], dec.eps)
        return (h @ P[f"{n}_tok_embed_weight"].T) / np.float32(
            dec.logits_scaling)
