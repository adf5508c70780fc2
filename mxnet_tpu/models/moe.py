"""Routed-expert decoder: window and global attention layers mixed, a
dense or a routed feed-forward block per layer, and a description that
tells the layer WHICH experts this program holds.

A decoder-only language model, pre-norm, RMSNorm, untied head:

    h = x + Attn_i(RMSNorm(x));   y = h + FFN_i(RMSNorm(h))
    logits = RMSNorm(y_L) W_head^T

``Attn_i`` is grouped-query attention with ``heads[i]`` query heads over
``kv_heads`` key-value heads of size ``head_dim``, no bias, scores scaled
``1/sqrt(head_dim)``, and (``gate``) a per-head output gate on the
layer's normed input ``u``:

    q = u W_q;  k = u W_k;  v = u W_v;  g = sigmoid(u W_g)       (heads[i])
    out = concat_h(g_h * softmax(mask(q_h k^T / sqrt(Dh))) v) W_o

With ``qk_norm`` every query and key head is RMS-normed over its
``head_dim`` dimensions with a learned gain of its own (``(head_dim,)``,
shared by the heads of a layer) before the rotary turn; without ``gate``
``g_h`` is 1.

``layer_types[i]`` picks the mask and the rotary scheme (``rope`` holds
one :class:`Rope` per kind): ``"full_attention"`` is causal over the
whole context; ``"sliding_attention"`` lets position ``t`` see keys
``t - window + 1 .. t``.  A :class:`Rope` rotates the first ``dim`` of a
head's ``head_dim`` dimensions (the rest pass through) with inverse
frequencies ``1 / base^(2j / dim)``, or, with ``yarn=(factor,
original_len, beta_fast, beta_slow, attention_factor)``, those blended
with ``1 / (factor base^(2j / dim))`` by the linear ramp between the
correction dimensions ``dim ln(original_len / (2 pi n)) / (2 ln base)``
at ``n = beta_fast`` (floor) and ``beta_slow`` (ceil), clamped to ``[0,
dim - 1]``; cosines and sines are multiplied by ``attention_factor``.

``ffn_types[i]`` is ``"dense"`` (SwiGLU ``d_model -> d_ff -> d_model``)
or ``"moe"``:

    FFN_i(u) = shared(u) + routed_scale * sum_{e in top_k} w_e E_e(u)

router logits ``u W_r`` over ``num_experts`` in float32, softmax over
all of them, the ``top_k`` largest, their weights divided by their sum;
``E_e`` and ``shared`` are SwiGLU blocks of width ``expert_ff`` /
``shared_ff`` (``shared_ff`` 0: no shared expert).  **Experts held**: the parameters carry experts
``[expert_offset, expert_offset + expert_count)`` only.  The router
still scores all ``num_experts`` and the ``top_k`` weights are
normalised over all picks, held or not; a pick of an absent expert adds
nothing (another program's share adds it).  The shared expert is whole.
With ``expert_count == num_experts`` the layer is the whole model's.

**Block diffusion** (``mask_id`` set): the model is a denoiser over blocks
of ``block`` positions.  Its mask is block-causal, a query at position
``p`` sees the keys at ``j < (p // block + 1) * block`` (both attention
kinds; bidirectional inside a block, causal across blocks), and its
logits are NOT shifted: those at a position give that position's token.
Generation (greedy, ``reference_generate``): the prompt's whole blocks are
prefilled; a block starts as the prompt's last ``P mod block`` tokens and
``mask_id`` elsewhere; a denoise pass runs the block against the cache
and itself, takes each masked position's argmax ``t_p`` (``mask_id``
excluded) and its confidence ``c_p``, the softmax probability of ``t_p``
over the vocabulary without ``mask_id``, and unmasks the ``n_s`` most
confident (ties to the lower position); a block of ``m`` masks takes
``n_s = m // steps + (s < m % steps)`` at its step ``s``.  When no mask
is left one more pass (the store pass) writes the clean block's K/V and
the block is committed.

This file holds the DESCRIPTION (:func:`moe_decoder`), seeded
parameters (:func:`init_params`) and the plain reference
(:func:`reference_logits`: float32, ``highest`` matmul precision, no
cache, no kernel, every held expert by a dense one-hot product).  It is
served by ``mx.serve.Engine(params, symbol=moe_decoder(...))`` through
``serve/hybrid.py``.  There is no Symbol here; training is out of scope.

Parameter names (a flat dict; ``(out, in)`` unless said):
``{name}_tok_embed_weight (V, D)``, ``{name}_head_weight (V, D)``,
``{name}_ln_f_gamma``; per layer ``_ln1_gamma``, ``_ln2_gamma``,
``_qkv_weight ((H_i + 2 Hkv) Dh, D)``, ``_gate_weight (H_i, D)`` (with
``gate``), ``_q_norm_gamma`` and ``_k_norm_gamma (Dh,)`` (with
``qk_norm``), ``_proj_weight (D, H_i Dh)``; dense layers ``_ff_in_weight (2 d_ff, D)``
(gate rows first), ``_ff_out_weight (D, d_ff)``; routed layers
``_router_weight (E, D)``, ``_shared_in_weight (2 Fs, D)`` and
``_shared_out_weight (D, Fs)`` (``shared_ff`` > 0), and, per held expert in ``(in, out)``
layout for the grouped products, ``_experts_in_weight (count, D, 2 F)``
(gate columns first), ``_experts_out_weight (count, F, D)``.
"""

from __future__ import annotations

import collections
import math

import numpy as np

import jax
import jax.numpy as jnp

__all__ = ["moe_decoder", "MoEDecoder", "Rope", "init_params",
           "reference_logits", "reference_routed", "reference_shared",
           "reference_generate", "transfer_counts"]

FULL, WINDOW = "full_attention", "sliding_attention"


class Rope(collections.namedtuple("Rope", ["dim", "base", "yarn"])):
    """One rotary scheme (hashable).  ``inv_freq()`` / ``scale`` are what
    ``serve/programs.py::_rope`` takes."""

    __slots__ = ()

    def inv_freq(self):
        """``(dim / 2,)`` float32 inverse frequencies (float64 arithmetic,
        rounded once)."""
        j = np.arange(0, self.dim, 2, dtype=np.float64)
        freqs = float(self.base) ** (j / self.dim)
        if self.yarn is None:
            return (1.0 / freqs).astype(np.float32)
        factor, orig, beta_fast, beta_slow, _ = self.yarn

        def corr(n):
            return (self.dim * math.log(orig / (n * 2.0 * math.pi))
                    / (2.0 * math.log(self.base)))

        low = max(math.floor(corr(beta_fast)), 0)
        high = min(math.ceil(corr(beta_slow)), self.dim - 1)
        if low == high:
            high += 0.001
        ramp = np.clip((np.arange(self.dim // 2, dtype=np.float64) - low)
                       / (high - low), 0.0, 1.0)
        extrapolated = 1.0 - ramp            # 1: the unscaled frequency
        inv = (1.0 / (factor * freqs)) * (1.0 - extrapolated) \
            + (1.0 / freqs) * extrapolated
        return inv.astype(np.float32)

    @property
    def scale(self):
        """What cosines and sines are multiplied by."""
        return 1.0 if self.yarn is None else float(self.yarn[4])


_FIELDS = ["vocab_size", "d_model", "layer_types", "heads", "kv_heads",
           "head_dim", "window", "ffn_types", "d_ff", "num_experts",
           "top_k", "expert_ff", "shared_ff", "routed_scale",
           "expert_offset", "expert_count", "rope", "eps", "name",
           "gate", "qk_norm", "block", "steps", "mask_id"]


class MoEDecoder(collections.namedtuple(
        "MoEDecoder", _FIELDS, defaults=(True, False, 1, 1, None))):
    """The static description (hashable: the serving programs close over
    it).  ``rope`` is ``((kind, Rope), ...)`` over the attention kinds;
    ``block`` / ``steps`` / ``mask_id`` describe block diffusion
    (``mask_id`` None: a decoder of one token a pass, ``block`` 1)."""

    __slots__ = ()

    # what serve/hybrid.py and the engine read of ANY description
    residual_multiplier = 1.0
    embedding_multiplier = 1.0
    logits_scaling = 1.0
    tied = False
    mamba_layers = ()
    kv_flat = False
    # its routed blocks: a softmax router, SwiGLU experts in the model width
    router_score = "softmax"
    expert_act = "swiglu"
    latent = 0

    @property
    def diffusion(self):
        """Whether a pass denoises a block (``mask_id`` set)."""
        return self.mask_id is not None

    @property
    def num_layers(self):
        return len(self.layer_types)

    @property
    def num_heads(self):
        """The widest layer's query heads (the engine's one number)."""
        return max(self.heads)

    @property
    def full_layers(self):
        return tuple(i for i, t in enumerate(self.layer_types) if t == FULL)

    @property
    def global_layers(self):
        """The layers whose K/V live in the one whole-context cache."""
        return self.full_layers

    @property
    def window_layers(self):
        return tuple(i for i, t in enumerate(self.layer_types)
                     if t == WINDOW)

    def rope_of(self, kind):
        return dict(self.rope)[kind]

    def param_shapes(self):
        """name -> shape of every parameter, in layer order."""
        n, D, Dh = self.name, self.d_model, self.head_dim
        out = {f"{n}_tok_embed_weight": (self.vocab_size, D)}
        for i, H in enumerate(self.heads):
            p = f"{n}_l{i}"
            out[f"{p}_ln1_gamma"] = (D,)
            out[f"{p}_qkv_weight"] = ((H + 2 * self.kv_heads) * Dh, D)
            if self.gate:
                out[f"{p}_gate_weight"] = (H, D)
            if self.qk_norm:
                out[f"{p}_q_norm_gamma"] = (Dh,)
                out[f"{p}_k_norm_gamma"] = (Dh,)
            out[f"{p}_proj_weight"] = (D, H * Dh)
            out[f"{p}_ln2_gamma"] = (D,)
            if self.ffn_types[i] == "dense":
                out[f"{p}_ff_in_weight"] = (2 * self.d_ff, D)
                out[f"{p}_ff_out_weight"] = (D, self.d_ff)
            else:
                out[f"{p}_router_weight"] = (self.num_experts, D)
                if self.shared_ff:
                    out[f"{p}_shared_in_weight"] = (2 * self.shared_ff, D)
                    out[f"{p}_shared_out_weight"] = (D, self.shared_ff)
                out[f"{p}_experts_in_weight"] = (
                    self.expert_count, D, 2 * self.expert_ff)
                out[f"{p}_experts_out_weight"] = (
                    self.expert_count, self.expert_ff, D)
        out[f"{n}_ln_f_gamma"] = (D,)
        out[f"{n}_head_weight"] = (self.vocab_size, D)
        return out

    def num_params(self):
        return sum(int(np.prod(s)) for s in self.param_shapes().values())

    def init_params(self, seed, dtype="float32"):
        return init_params(self, seed, dtype)

    def reference_logits(self, params, tokens, block=None):
        return reference_logits(self, params, tokens, block=block)

    def reference_generate(self, params, prompt, max_new_tokens):
        return reference_generate(self, params, prompt, max_new_tokens)


def moe_decoder(vocab_size, d_model, layer_types, heads, kv_heads, head_dim,
                window, ffn_types, d_ff, num_experts, top_k, expert_ff,
                shared_ff, routed_scale=1.0, experts_held=None, rope=None,
                eps=1e-6, name="moe", gate=True, qk_norm=False,
                diffusion=None):
    """Describe a routed-expert decoder for ``serve.Engine(params,
    symbol=...)``.

    ``layer_types``: ``"full_attention"`` / ``"sliding_attention"`` per
    layer; ``heads``: query heads per layer; ``ffn_types``: ``"dense"`` /
    ``"moe"`` per layer.  ``experts_held``: ``(offset, count)``, the
    experts whose weights this program carries (default: all).  ``rope``:
    ``{kind: Rope}`` (default: the whole head at base 10000 for both).
    ``gate``: the per-head output gate; ``qk_norm``: per-head RMS norms of
    queries and keys; ``shared_ff`` 0: no shared expert.  ``diffusion``:
    ``(block, steps, mask_id)`` for a block-diffusion decoder (the
    module's docstring), None for one token a pass."""
    layer_types = tuple(str(t) for t in layer_types)
    ffn_types = tuple(str(t) for t in ffn_types)
    heads = tuple(int(h) for h in heads)
    bad = sorted(set(layer_types) - {FULL, WINDOW})
    if bad or not layer_types:
        raise ValueError(f"moe_decoder: layer_types must name '{FULL}' or "
                         f"'{WINDOW}' per layer (got {bad or 'none'})")
    if sorted(set(ffn_types) - {"dense", "moe"}):
        raise ValueError("moe_decoder: ffn_types must name 'dense' or "
                         "'moe' per layer")
    if not len(layer_types) == len(heads) == len(ffn_types):
        raise ValueError("moe_decoder: layer_types, heads and ffn_types "
                         "must have one entry per layer")
    if any(h % kv_heads for h in heads):
        raise ValueError(f"moe_decoder: heads={heads} are not all "
                         f"multiples of kv_heads={kv_heads}")
    if WINDOW in layer_types and int(window) < 1:
        raise ValueError("moe_decoder: window layers need window >= 1")
    offset, count = (0, num_experts) if experts_held is None \
        else (int(experts_held[0]), int(experts_held[1]))
    if not (0 <= offset and count >= 1 and offset + count <= num_experts):
        raise ValueError(f"moe_decoder: experts_held=({offset}, {count}) "
                         f"does not lie in [0, {num_experts})")
    if not 1 <= top_k <= num_experts:
        raise ValueError(f"moe_decoder: top_k={top_k} of {num_experts}")
    block, steps, mask_id = (1, 1, None) if diffusion is None \
        else (int(diffusion[0]), int(diffusion[1]), int(diffusion[2]))
    if diffusion is not None and not (block >= 2 and steps >= 1
                                      and 0 <= mask_id < vocab_size):
        raise ValueError(f"moe_decoder: diffusion=({block}, {steps}, "
                         f"{mask_id}) needs a block of 2 or more, a step "
                         f"and a mask id inside the vocabulary")
    if diffusion is not None and WINDOW in layer_types:
        raise ValueError("moe_decoder: a diffusion decoder's window layers "
                         "are not written (a block's positions share one "
                         "paged query)")
    rope = dict(rope or {})
    for kind in (FULL, WINDOW):
        r = rope.setdefault(kind, Rope(int(head_dim), 10000.0, None))
        if r.dim % 2 or not 0 < r.dim <= head_dim:
            raise ValueError(f"moe_decoder: rope[{kind!r}] rotates "
                             f"{r.dim} of {head_dim} dimensions")
    return MoEDecoder(
        int(vocab_size), int(d_model), layer_types, heads, int(kv_heads),
        int(head_dim), int(window), ffn_types, int(d_ff), int(num_experts),
        int(top_k), int(expert_ff), int(shared_ff), float(routed_scale),
        offset, count, tuple(sorted(rope.items())), float(eps), str(name),
        bool(gate), bool(qk_norm), block, steps, mask_id)


def init_params(dec, seed, dtype="float32"):
    """Random parameters made on the device from the seed in ONE jitted
    call, in the dtype they are served in: matrices N(0, 1/fan_in), norm
    gains 1.  The seed enters as data."""
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
            w = jax.random.normal(k, shape, jnp.float32)
            out[name] = (w * np.float32(fan_in ** -0.5)).astype(dtype)
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


def _swiglu(u, w_in, w_out):
    """``(out, in)`` weights, gate rows first."""
    gu = u @ w_in.T
    F = w_in.shape[0] // 2
    return (_silu(gu[:, :F]) * gu[:, F:]) @ w_out.T


def _ref_rotate(x, rope):
    """``x (T, H, Dh)`` at positions ``0 .. T-1``."""
    T, half = x.shape[0], rope.dim // 2
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * rope.inv_freq()
    cos = (jnp.cos(ang) * np.float32(rope.scale))[:, None, :]
    sin = (jnp.sin(ang) * np.float32(rope.scale))[:, None, :]
    x1, x2 = x[..., :half], x[..., half:rope.dim]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos,
                            x[..., rope.dim:]], axis=-1)


def _ref_attention(dec, P, i, u, block=1):
    p = f"{dec.name}_l{i}"
    T, H, Hkv, Dh = u.shape[0], dec.heads[i], dec.kv_heads, dec.head_dim
    kind = dec.layer_types[i]
    qkv = u @ P[f"{p}_qkv_weight"].T
    q = qkv[:, :H * Dh].reshape(T, H, Dh)
    k = qkv[:, H * Dh:(H + Hkv) * Dh].reshape(T, Hkv, Dh)
    v = qkv[:, (H + Hkv) * Dh:].reshape(T, Hkv, Dh)
    if dec.qk_norm:
        q = _rms(q, P[f"{p}_q_norm_gamma"], dec.eps)
        k = _rms(k, P[f"{p}_k_norm_gamma"], dec.eps)
    rope = dec.rope_of(kind)
    q, k = _ref_rotate(q, rope), _ref_rotate(k, rope)
    s = jnp.einsum("qkgd,skd->kgqs", q.reshape(T, Hkv, H // Hkv, Dh), k) \
        * np.float32(1.0 / math.sqrt(Dh))
    t, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    keep = j < (t // block + 1) * block
    if kind == WINDOW:
        keep = jnp.logical_and(keep, j > t - dec.window)
    s = jnp.where(keep[None, None], s, -jnp.inf)
    a = jnp.einsum("kgqs,skd->qkgd", jax.nn.softmax(s, axis=-1), v)
    a = a.reshape(T, H, Dh)
    if dec.gate:
        gate = jax.nn.sigmoid(u @ P[f"{p}_gate_weight"].T)    # (T, H)
        a = a * gate[:, :, None]
    return a.reshape(T, H * Dh) @ P[f"{p}_proj_weight"].T


def reference_shared(dec, P, i, u):
    """The shared expert of layer ``i`` over rows ``u (T, D)`` (zero
    without one)."""
    p = f"{dec.name}_l{i}"
    if not dec.shared_ff:
        return jnp.zeros_like(u)
    return _swiglu(u, P[f"{p}_shared_in_weight"],
                   P[f"{p}_shared_out_weight"])


def reference_routed(dec, P, i, u):
    """This share's routed part of layer ``i``: ``routed_scale * sum over
    the HELD picks of w_e E_e(u)``, the weights normalised over all
    ``top_k`` picks.  Every held expert runs on every row; a one-hot
    product keeps each row's own."""
    p = f"{dec.name}_l{i}"
    logits = u @ P[f"{p}_router_weight"].T                    # (T, E)
    w, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), dec.top_k)
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    # (T, count): the weight each held expert has on each row
    held = jnp.arange(dec.expert_count)[None, None, :] + dec.expert_offset
    share = jnp.sum(jnp.where(idx[:, :, None] == held, w[:, :, None], 0.0),
                    axis=1)
    w_in, w_out = P[f"{p}_experts_in_weight"], P[f"{p}_experts_out_weight"]
    out = jnp.zeros_like(u)
    for e in range(dec.expert_count):
        gu = u @ w_in[e]
        F = dec.expert_ff
        out = out + share[:, e:e + 1] * (
            (_silu(gu[:, :F]) * gu[:, F:]) @ w_out[e])
    return np.float32(dec.routed_scale) * out


def reference_logits(dec, params, tokens, taps=None, block=None):
    """Logits (T, V) of one sequence's full forward pass: the equations of
    this module's docstring in float32 under
    ``jax.default_matmul_precision("highest")``, no cache, no kernel.
    The description says which experts ``params`` carries: the share, or
    (``expert_count == num_experts``) the whole.  ``taps``: a dict that
    receives ``layer -> the feed-forward block's normed input (T, D)``.
    ``block``: the mask's block length (default the description's; 1 is
    causal)."""
    block = dec.block if block is None else int(block)
    toks = jnp.asarray(np.asarray(tokens), jnp.int32)
    P = {k: jnp.asarray(v).astype(jnp.float32) for k, v in params.items()}
    n = dec.name
    with jax.default_matmul_precision("highest"):
        h = P[f"{n}_tok_embed_weight"][toks]
        for i in range(dec.num_layers):
            p = f"{n}_l{i}"
            h = h + _ref_attention(dec, P, i,
                                   _rms(h, P[f"{p}_ln1_gamma"], dec.eps),
                                   block)
            u = _rms(h, P[f"{p}_ln2_gamma"], dec.eps)
            if taps is not None:
                taps[i] = u
            if dec.ffn_types[i] == "dense":
                h = h + _swiglu(u, P[f"{p}_ff_in_weight"],
                                P[f"{p}_ff_out_weight"])
            else:
                h = h + reference_shared(dec, P, i, u) \
                    + reference_routed(dec, P, i, u)
        h = _rms(h, P[f"{n}_ln_f_gamma"], dec.eps)
        return h @ P[f"{n}_head_weight"].T


def transfer_counts(masks, steps):
    """How many of a block's ``masks`` masked positions each of its
    ``steps`` denoise steps unmasks: ``masks // steps`` each, one more in
    the first ``masks % steps`` (a block of no mask takes none)."""
    base, extra = divmod(int(masks), int(steps))
    return [base + (s < extra) for s in range(int(steps))]


def _confident(logits, mask_id):
    """Each position's greedy token with ``mask_id`` excluded, and its
    confidence: its softmax probability over the vocabulary without
    ``mask_id`` (float64 on the host)."""
    lg = np.asarray(logits, np.float64).copy()
    lg[:, mask_id] = -np.inf
    tok = lg.argmax(-1)
    top = lg.max(-1)
    conf = 1.0 / np.exp(lg - top[:, None]).sum(-1)
    return tok, conf


def reference_generate(dec, params, prompt, max_new_tokens):
    """Greedy block-diffusion generation by the plain reference (the
    module's docstring): every pass a full forward of the committed
    sequence and the current block under the block-causal mask.  Returns
    ``{"tokens": [...]`` (exactly ``max_new_tokens``), ``"steps"``: one
    entry a denoise pass, ``(block start, the block as it went in (B,),
    its logits (B, V) float32, [(position, token), ...] unmasked)``,
    ``"unmasked"``: every ``(position, token, step)`` in order}."""
    if not dec.diffusion:
        raise ValueError("reference_generate: not a diffusion decoder")
    B, T, mask = dec.block, dec.steps, dec.mask_id
    prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
    if len(prompt) < B or mask in prompt:
        raise ValueError("reference_generate: a prompt of at least one "
                         "block, without the mask id")
    r = len(prompt) % B
    seq = prompt[:len(prompt) - r]
    fixed = prompt[len(prompt) - r:]
    out, steps, log = [], [], []
    while len(out) < max_new_tokens:
        blk = np.asarray(fixed + [mask] * (B - len(fixed)), np.int64)
        counts = transfer_counts(B - len(fixed), T)
        start = len(seq)
        for s, n in enumerate(counts):
            masked = blk == mask
            if not masked.any():
                break
            lg = reference_logits(dec, params, seq + list(blk))[start:]
            tok, conf = _confident(lg, mask)
            cand = np.where(masked, conf, -1.0)
            order = np.argsort(-cand, kind="stable")[:n]
            went_in = blk.copy()
            got = []
            for b in sorted(order):
                blk[b] = tok[b]
                got.append((start + int(b), int(tok[b])))
                log.append((start + int(b), int(tok[b]), s))
            steps.append((start, went_in, np.asarray(lg, np.float32), got))
        out += [int(t) for t in blk[len(fixed):]]
        seq += [int(t) for t in blk]
        fixed = []
    return {"tokens": out[:max_new_tokens], "steps": steps, "unmasked": log}
