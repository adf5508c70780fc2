"""One-branch decoder: every layer is a sequence mixer OR a feed-forward
part, never both.

A decoder-only language model, pre-norm, RMSNorm, no bias on any linear
map, untied head, no positional encoding:

    h_0 = E[token]
    h_i = h_{i-1} + Block_i(RMSNorm_i(h_{i-1}))        ONE branch a layer
    logits = RMSNorm_f(h_L) W_head^T

``layers[i]`` names the block: ``"mamba"``, ``"attention"`` or ``"moe"``.

**mamba**: a Mamba-2 mixer of H heads of size P in G state GROUPS
(``d_inner = H P``, state size N, kernel K; head ``h`` belongs to group
``g = h // (H / G)``):

    [z | xBC | dt] = W_in u                 rows d_inner | d_inner + 2 G N | H
    xBC_t = silu(b + sum_j w_j * xBC_{t-K+1+j})       (depthwise, causal)
    x = xBC[:d_inner];  B = xBC[d_inner : d_inner + G N] as (G, N);  C the rest
    dt_t  = softplus(dt_t + dt_bias);  a_t = exp(-exp(A_log) * dt_t)
    S_t[h] = a_t[h] S_{t-1}[h] + dt_t[h] * x_t[h] (x) B_t[g]
    y_t[h] = S_t[h] C_t[g] + D[h] * x_t[h]
    out    = W_out GroupRMSNorm_G(y_t * silu(z_t))

(gate first, then an RMS over each group's ``d_inner / G`` channels, one
gain vector of ``d_inner``).

**attention**: grouped-query causal attention, ``num_heads`` query heads
over ``kv_heads`` key-value heads of ``head_dim``, scores times ``1 /
sqrt(head_dim)``, no rotary.

**moe**: routed experts in a LATENT space plus one shared expert:

    s = sigmoid(u W_r)  over all E experts, float32
    picks = the top_k largest of s + b       (b: the selection bias)
    w_e = routed_scale * s_e / sum_{picked} s
    l = W_down u                              (D -> latent)
    r = sum_{e picked} w_e E_e(l)             E_e(l) = W2_e relu(W1_e l)^2
    Block(u) = W_up r + shared(u)             shared(u) = Ws2 relu(Ws1 u)^2

The bias moves picks and never a weight; an expert is two matrices
(ungated).  **Experts held**: the parameters carry experts ``[expert_offset,
expert_offset + expert_count)`` only.  The router still scores all
``num_experts`` and the ``top_k`` weights are normalised over all picks,
held or not; ``r`` sums the held picks only, and ``W_up``, being linear,
is applied to that partial sum (the shares of a deployment reduce their
partial ``r`` at the latent width).  The shared expert is whole.

This file holds the DESCRIPTION (:func:`branch_decoder`), seeded
parameters (:func:`init_params`) and the plain reference
(:func:`reference_logits`: float32, ``highest`` matmul precision, the
recurrence token by token, every held expert by a dense one-hot product,
no cache, no chunking, no kernel).  It is served by
``mx.serve.Engine(params, symbol=branch_decoder(...))`` through
``serve/hybrid.py``.  There is no Symbol here; training is out of scope.

Parameter names (a flat dict; ``(out, in)`` unless said):
``{name}_tok_embed_weight (V, D)``, ``{name}_head_weight (V, D)``,
``{name}_ln_f_gamma``; a mixer layer's norm ``_ln1_gamma``, a
feed-forward layer's ``_ln2_gamma``; attention ``_qkv_weight ((Hq + 2
Hkv) Dh, D)``, ``_proj_weight (D, Hq Dh)``; mamba ``_in_proj_weight (2
d_inner + 2 G N + H, D)``, ``_conv_weight (d_inner + 2 G N, K)``,
``_conv_bias``, ``_dt_bias (H)``, ``_A_log (H)``, ``_D (H)`` (the three
float32 whatever the dtype), ``_norm_gamma (d_inner)``,
``_out_proj_weight (D, d_inner)``; moe ``_router_weight (E, D)``,
``_router_bias (E)`` (float32), ``_latent_down_weight (L, D)``,
``_latent_up_weight (D, L)``, ``_shared_in_weight (Fs, D)``,
``_shared_out_weight (D, Fs)`` and, per held expert in ``(in, out)``
layout for the grouped products, ``_experts_in_weight (count, L, F)``,
``_experts_out_weight (count, F, L)``.
"""

from __future__ import annotations

import collections

import numpy as np

import jax
import jax.numpy as jnp

from .hybrid import _ref_attention, _ref_mamba, _rms

__all__ = ["branch_decoder", "BranchDecoder", "init_params",
           "reference_logits", "reference_routed", "reference_shared"]

_FIELDS = ["vocab_size", "d_model", "layer_types", "ffn_types", "num_heads",
           "kv_heads", "head_dim", "mamba_heads",
           "mamba_head_dim", "mamba_state", "mamba_groups", "mamba_conv",
           "mamba_chunk", "num_experts", "top_k", "expert_ff", "latent",
           "shared_ff", "routed_scale", "expert_offset", "expert_count",
           "eps", "name"]

# float32 whatever dtype the model is served in
_F32_PARAMS = ("_dt_bias", "_A_log", "_D", "_router_bias")


class BranchDecoder(collections.namedtuple("BranchDecoder", _FIELDS)):
    """The static description (hashable: the serving programs close over
    it).  A layer is its mixer, ``layer_types[i]`` (``"mamba"`` /
    ``"attention"``), or its feed-forward part, ``ffn_types[i]``
    (``"moe"``); the other of the two is ``"none"``."""

    __slots__ = ()

    # what serve/hybrid.py and the engine read of ANY description
    residual_multiplier = 1.0
    embedding_multiplier = 1.0
    logits_scaling = 1.0
    tied = False
    window_layers = ()
    kv_flat = True
    diffusion = False           # one token a pass
    # its routed blocks: sigmoids with a selection bias, ungated
    # squared-ReLU experts (in the ``latent`` width)
    router_score = "sigmoid"
    expert_act = "relu2"

    @property
    def layers(self):
        """Each layer's one block: ``"mamba"``, ``"attention"``, ``"moe"``."""
        return tuple(f if m == "none" else m
                     for m, f in zip(self.layer_types, self.ffn_types))

    @property
    def num_layers(self):
        return len(self.layer_types)

    @property
    def d_inner(self):
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_dim(self):
        """Width of the convolution's input: x, and B and C of every
        group."""
        return self.d_inner + 2 * self.mamba_groups * self.mamba_state

    @property
    def attention_multiplier(self):
        return 1.0 / float(np.sqrt(self.head_dim))

    def _of(self, kind):
        return tuple(i for i, t in enumerate(self.layers) if t == kind)

    @property
    def attention_layers(self):
        return self._of("attention")

    @property
    def global_layers(self):
        """The layers whose K/V live in the one whole-context cache."""
        return self.attention_layers

    @property
    def mamba_layers(self):
        return self._of("mamba")

    @property
    def moe_layers(self):
        return self._of("moe")

    def param_shapes(self):
        """name -> shape of every parameter, in layer order."""
        n, D = self.name, self.d_model
        W = self.latent
        out = {f"{n}_tok_embed_weight": (self.vocab_size, D)}
        qkv = (self.num_heads + 2 * self.kv_heads) * self.head_dim
        for i, kind in enumerate(self.layers):
            p = f"{n}_l{i}"
            if kind == "moe":
                out[f"{p}_ln2_gamma"] = (D,)
                out[f"{p}_router_weight"] = (self.num_experts, D)
                out[f"{p}_router_bias"] = (self.num_experts,)
                out[f"{p}_latent_down_weight"] = (W, D)
                out[f"{p}_latent_up_weight"] = (D, W)
                out[f"{p}_shared_in_weight"] = (self.shared_ff, D)
                out[f"{p}_shared_out_weight"] = (D, self.shared_ff)
                out[f"{p}_experts_in_weight"] = (
                    self.expert_count, W, self.expert_ff)
                out[f"{p}_experts_out_weight"] = (
                    self.expert_count, self.expert_ff, W)
                continue
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
        out[f"{n}_ln_f_gamma"] = (D,)
        out[f"{n}_head_weight"] = (self.vocab_size, D)
        return out

    def param_dtype(self, name, dtype):
        """The dtype parameter ``name`` has in a model served in
        ``dtype``."""
        return (jnp.dtype(jnp.float32) if name.endswith(_F32_PARAMS)
                else jnp.dtype(dtype))

    def num_params(self):
        return sum(int(np.prod(s)) for s in self.param_shapes().values())

    def init_params(self, seed, dtype="float32"):
        return init_params(self, seed, dtype)

    def reference_logits(self, params, tokens, taps=None):
        return reference_logits(self, params, tokens, taps)


def branch_decoder(vocab_size, d_model, layers, num_heads, kv_heads,
                   head_dim, mamba_heads, mamba_head_dim, mamba_state,
                   num_experts, top_k, expert_ff, shared_ff, latent,
                   mamba_groups=1, mamba_conv=4, mamba_chunk=128,
                   routed_scale=1.0, experts_held=None, eps=1e-5,
                   name="branch"):
    """Describe a one-branch decoder for ``serve.Engine(params,
    symbol=...)``.

    ``layers``: ``"mamba"`` / ``"attention"`` / ``"moe"`` per layer.
    ``experts_held``: ``(offset, count)``, the experts whose weights this
    program carries (default: all).  ``latent``: the width the routed
    experts work in."""
    layers = tuple(str(t) for t in layers)
    bad = sorted(set(layers) - {"mamba", "attention", "moe"})
    if bad or not layers:
        raise ValueError(f"branch_decoder: layers must name 'mamba', "
                         f"'attention' or 'moe' per layer (got "
                         f"{bad or 'none'})")
    if num_heads % kv_heads:
        raise ValueError(f"branch_decoder: num_heads={num_heads} is not a "
                         f"multiple of kv_heads={kv_heads}")
    if mamba_heads % mamba_groups:
        raise ValueError(f"branch_decoder: mamba_heads={mamba_heads} do not "
                         f"divide into mamba_groups={mamba_groups}")
    if latent < 1:
        raise ValueError(f"branch_decoder: latent={latent}")
    offset, count = (0, num_experts) if experts_held is None \
        else (int(experts_held[0]), int(experts_held[1]))
    if not (0 <= offset and count >= 1 and offset + count <= num_experts):
        raise ValueError(f"branch_decoder: experts_held=({offset}, "
                         f"{count}) does not lie in [0, {num_experts})")
    if not 1 <= top_k <= num_experts:
        raise ValueError(f"branch_decoder: top_k={top_k} of {num_experts}")
    return BranchDecoder(
        int(vocab_size), int(d_model),
        tuple("none" if t == "moe" else t for t in layers),
        tuple("moe" if t == "moe" else "none" for t in layers),
        int(num_heads), int(kv_heads), int(head_dim),
        int(mamba_heads), int(mamba_head_dim), int(mamba_state),
        int(mamba_groups), int(mamba_conv), int(mamba_chunk),
        int(num_experts), int(top_k), int(expert_ff), int(latent),
        int(shared_ff), float(routed_scale), offset, count, float(eps),
        str(name))


def init_params(dec, seed, dtype="float32"):
    """Random parameters made on the device from the seed in ONE jitted
    call, in the dtype they are served in.  Matrices are N(0, 1/fan_in)
    (an expert's fan in is its rows' width), norm gains 1; the
    convolution N(0, 1/K) with bias N(0, 0.01); the selection bias N(0,
    0.01); and, as the Mamba-2 reference initialises them, ``A_log = log
    U(1, 16)``, ``dt_bias`` the inverse softplus of ``U(0.001, 0.1)`` and
    ``D = 1``.  The seed enters as data: every seed runs the same
    compiled program."""
    shapes = dec.param_shapes()

    def make(key):
        out = {}
        for i, (name, shape) in enumerate(shapes.items()):
            k = jax.random.fold_in(key, i)
            dt = dec.param_dtype(name, dtype)
            if name.endswith("gamma"):
                out[name] = jnp.ones(shape, dt)
            elif name.endswith("_D"):
                out[name] = jnp.ones(shape, dt)
            elif name.endswith("_A_log"):
                out[name] = jnp.log(jax.random.uniform(
                    k, shape, jnp.float32, 1.0, 16.0))
            elif name.endswith("_dt_bias"):
                step = jax.random.uniform(k, shape, jnp.float32, 1e-3, 1e-1)
                out[name] = step + jnp.log(-jnp.expm1(-step))
            elif name.endswith(("_conv_bias", "_router_bias")):
                out[name] = (jax.random.normal(k, shape, jnp.float32)
                             * np.float32(0.1)).astype(dt)
            else:
                fan_in = shape[-2] if "_experts_" in name else shape[-1]
                w = jax.random.normal(k, shape, jnp.float32)
                out[name] = (w * np.float32(fan_in ** -0.5)).astype(dt)
        return out

    # hardware bit generator: billions of values by threefry take long
    key = jax.random.key(int(seed) % (2 ** 31), impl="unsafe_rbg")
    key = jax.random.fold_in(key, int(seed) >> 31)
    return jax.jit(make)(key)


# -- the plain reference ---------------------------------------------------------
# (the attention and the grouped Mamba-2 mixer are models/hybrid.py's)

def _relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def reference_shared(dec, P, i, u):
    """The shared expert of layer ``i`` over rows ``u (T, D)``."""
    p = f"{dec.name}_l{i}"
    return _relu2(u @ P[f"{p}_shared_in_weight"].T) \
        @ P[f"{p}_shared_out_weight"].T


def reference_picks(dec, P, i, u):
    """``(idx (T, k), w (T, k))``: layer ``i``'s picks and their weights
    (normalised over all ``top_k`` picks, times ``routed_scale``)."""
    p = f"{dec.name}_l{i}"
    logits = u @ P[f"{p}_router_weight"].T                    # (T, E)
    s = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(s + P[f"{p}_router_bias"][None, :], dec.top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    return idx, np.float32(dec.routed_scale) * w / jnp.sum(
        w, axis=-1, keepdims=True)


def reference_routed(dec, P, i, u):
    """This share's routed part of layer ``i``: the weighted sum over the
    HELD picks in the latent, projected up.  Every held expert runs on every row; a one-hot product
    keeps each row's own."""
    p = f"{dec.name}_l{i}"
    idx, w = reference_picks(dec, P, i, u)
    # (T, count): the weight each held expert has on each row
    held = jnp.arange(dec.expert_count)[None, None, :] + dec.expert_offset
    share = jnp.sum(jnp.where(idx[:, :, None] == held, w[:, :, None], 0.0),
                    axis=1)
    lat = u @ P[f"{p}_latent_down_weight"].T
    w_in, w_out = P[f"{p}_experts_in_weight"], P[f"{p}_experts_out_weight"]
    r = jnp.zeros_like(lat)
    for e in range(dec.expert_count):
        r = r + share[:, e:e + 1] * (_relu2(lat @ w_in[e]) @ w_out[e])
    return r @ P[f"{p}_latent_up_weight"].T


def reference_logits(dec, params, tokens, taps=None):
    """Logits (T, V) of one sequence's full forward pass: the equations of
    this module's docstring in float32 under
    ``jax.default_matmul_precision("highest")``, no cache, no chunking,
    no kernel.  The description says which experts ``params`` carries:
    the share, or (``expert_count == num_experts``) the whole.  ``taps``:
    a dict that receives ``layer -> a routed block's normed input (T,
    D)``."""
    toks = jnp.asarray(np.asarray(tokens), jnp.int32)
    P = {k: jnp.asarray(v).astype(jnp.float32) for k, v in params.items()}
    n = dec.name
    with jax.default_matmul_precision("highest"):
        h = P[f"{n}_tok_embed_weight"][toks]
        for i, kind in enumerate(dec.layers):
            p = f"{n}_l{i}"
            if kind == "moe":
                u = _rms(h, P[f"{p}_ln2_gamma"], dec.eps)
                if taps is not None:
                    taps[i] = u
                h = h + reference_shared(dec, P, i, u) \
                    + reference_routed(dec, P, i, u)
                continue
            u = _rms(h, P[f"{p}_ln1_gamma"], dec.eps)
            mix = _ref_attention if kind == "attention" else _ref_mamba
            h = h + mix(dec, P, p, u)
        h = _rms(h, P[f"{n}_ln_f_gamma"], dec.eps)
        return h @ P[f"{n}_head_weight"].T
