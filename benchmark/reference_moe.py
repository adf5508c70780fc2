"""The plain reference of a routed-expert decoder with window and global
attention layers, the benchmark's own copy: it shares no code with
``mxnet_tpu`` and reads the program's parameter dict by the names
``mx.models.moe_decoder`` gives its parameters.  ``cfg`` is the
configuration file's dictionary (the source's keys).

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no cache, no kernel, no
bucket padding, no sorting of picks; every held expert runs on every row
and a row keeps its own picks' share.

    h = x + Attn_i(RMSNorm(x));  y = h + FFN_i(RMSNorm(h));  eps 1e-6
    logits = RMSNorm(y_L) W_head^T                     (untied head)

    Attn_i: H_i = num_attention_heads_per_layer[i] query heads over 8
      key-value heads of 128, no bias, scores / sqrt(128);
      g = sigmoid(u W_g) (one value a query head, u the layer's normed
      input) multiplies each head's attention output before W_o.
      full_attention: causal; rotary on the first 64 of 128 dimensions,
        YaRN: inverse frequencies 1/b^(2j/64) and 1/(factor b^(2j/64))
        blended by the linear ramp between the correction dimensions
        64 ln(orig / (2 pi n)) / (2 ln b) at n = beta_fast (floor) and
        beta_slow (ceil), clamped to [0, 63]; cos and sin times
        attention_factor.
      sliding_attention: position t sees keys t - window + 1 .. t; rotary
        on all 128 dimensions at base 10000.
    FFN_0: SwiGLU 3072 -> 12288 -> 3072 (layers in mlp_only_layers).
    FFN_i: shared(u) + routed_scale * sum_{e in top10} w_e E_e(u): router
      logits u W_r over all num_experts in float32, softmax, the ten
      largest, divided by their sum; E_e and shared SwiGLU of 1024.  Only
      experts [expert_offset, expert_offset + num_experts_held) are here:
      a pick of another adds nothing.

So that 17 k positions at full width fit on the chip, weights are upcast
a layer (an expert) at a time, attention walks the keys in blocks with a
running softmax, and the head runs on the generated positions only.

``fault`` (``moe_controls.py``) computes a deliberately different model;
``None`` is the configuration's.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
NAME = "moe"
FAULTS = ("window_496", "held_norm", "no_yarn", "router_bf16", "acc_bf16")


def _f32(x):
    return jnp.asarray(x).astype(F32)


def _rms(x, gamma, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gamma


def _silu(x):
    return x * jax.nn.sigmoid(x)


def dims(cfg):
    """The sizes the equations need, from the source's keys."""
    L = cfg["num_hidden_layers"]
    assert not cfg["attention_bias"] and not cfg["tie_word_embeddings"]
    assert cfg["norm_topk_prob"] and cfg["gating"] == "per-head"
    assert not cfg["moe_apply_router_weight_on_input"]
    assert not cfg["moe_router_logit_softcapping"]
    kinds = tuple(cfg["layer_types"][:L])
    assert set(kinds) <= {"full_attention", "sliding_attention"}
    return {"D": cfg["hidden_size"], "V": cfg["vocab_size"], "L": L,
            "Hkv": cfg["num_key_value_heads"], "Dh": cfg["head_dim"],
            "heads": tuple(cfg["num_attention_heads_per_layer"][:L]),
            "kinds": kinds, "window": cfg["sliding_window"],
            "dense": tuple(i in cfg["mlp_only_layers"] for i in range(L)),
            "F_dense": cfg["intermediate_size"],
            "E": cfg["num_experts"], "k": cfg["num_experts_per_tok"],
            "F": cfg["moe_intermediate_size"],
            "Fs": cfg["shared_expert_intermediate_size"],
            "scale": cfg["moe_routed_scaling_factor"],
            "offset": cfg["expert_offset"], "held": cfg["num_experts_held"],
            "eps": cfg["rms_norm_eps"]}


def inv_freq(rope, head_dim, blend=True):
    """(rotated dimensions, float32 inverse frequencies, what cos and sin
    are multiplied by) of one entry of ``rope_parameters``."""
    dim = int(round(head_dim * rope["partial_rotary_factor"]))
    base = float(rope["rope_theta"])
    freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rope["rope_type"] == "default":
        return dim, (1.0 / freqs).astype(np.float32), 1.0
    assert rope["rope_type"] == "yarn"
    if not blend:
        return dim, (1.0 / freqs).astype(np.float32), \
            rope["attention_factor"]
    orig = rope["original_max_position_embeddings"]

    def corr(n):
        return dim * math.log(orig / (n * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr(rope["beta_fast"])), 0)
    high = min(math.ceil(corr(rope["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0, 1)
    inv = ramp / (rope["factor"] * freqs) + (1 - ramp) / freqs
    return dim, inv.astype(np.float32), rope["attention_factor"]


def _rotate(x, dim, inv, scale):
    T, half = x.shape[0], dim // 2
    ang = jnp.arange(T, dtype=F32)[:, None] * jnp.asarray(inv, F32)
    cos = (jnp.cos(ang) * F32(scale))[:, None, :]
    sin = (jnp.sin(ang) * F32(scale))[:, None, :]
    a, b = x[..., :half], x[..., half:dim]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos,
                            x[..., dim:]], -1)


@functools.partial(jax.jit, static_argnames=("H", "Hkv", "Dh", "window",
                                             "rope", "block"))
def _attention(u, w, H, Hkv, Dh, window, rope, block):
    """One attention layer over rows ``u (T, D)`` at positions 0 .. T-1.
    ``window`` 0: causal over everything.  ``rope``: (dim, inverse
    frequencies as a tuple, scale).  Queries are taken ``block`` at a
    time and walk the key blocks they can see under a running max and
    sum, so no (T, T) array exists."""
    T = u.shape[0]
    dim, inv, scale = rope
    qkv = u @ _f32(w["qkv_weight"]).T
    q = _rotate(qkv[:, :H * Dh].reshape(T, H, Dh), dim, inv, scale)
    k = _rotate(qkv[:, H * Dh:(H + Hkv) * Dh].reshape(T, Hkv, Dh), dim,
                inv, scale)
    v = qkv[:, (H + Hkv) * Dh:].reshape(T, Hkv, Dh)
    G = H // Hkv
    q = q.reshape(T // block, block, Hkv, G, Dh) * F32(1.0 / math.sqrt(Dh))

    def rows(args):
        """Query block ``i`` over the key blocks it can see."""
        i, qb = args
        t = i * block + jnp.arange(block)[:, None]

        def fold(j, carry):
            m, l, acc = carry
            kb = jax.lax.dynamic_slice_in_dim(k, j * block, block, 0)
            vb = jax.lax.dynamic_slice_in_dim(v, j * block, block, 0)
            s = jnp.einsum("qkgd,skd->qkgs", qb, kb)
            pos = j * block + jnp.arange(block)[None, :]
            keep = pos <= t
            if window:
                keep = jnp.logical_and(keep, pos > t - window)
            s = jnp.where(keep[:, None, None, :], s, -jnp.inf)
            m_new = jnp.maximum(m, s.max(-1))
            # every key of a block may be masked for a row: exp(-inf - 0)
            safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(s - safe[..., None])
            alpha = jnp.exp(m - safe)
            return (m_new, l * alpha + p.sum(-1),
                    acc * alpha[..., None]
                    + jnp.einsum("qkgs,skd->qkgd", p, vb))

        first = jnp.maximum(i * block - window + 1, 0) // block if window \
            else 0
        init = (jnp.full((block, Hkv, G), -jnp.inf, F32),
                jnp.zeros((block, Hkv, G), F32),
                jnp.zeros((block, Hkv, G, Dh), F32))
        _, l, acc = jax.lax.fori_loop(first, i + 1, fold, init)
        return acc / l[..., None]

    at = jax.lax.map(rows, (jnp.arange(T // block), q)).reshape(T, H, Dh)
    gate = jax.nn.sigmoid(u @ _f32(w["gate_weight"]).T)
    return (at * gate[:, :, None]).reshape(T, H * Dh) \
        @ _f32(w["proj_weight"]).T


def _to_bf16(x):
    """Round float32 values to what bfloat16 holds, and stay float32.  By
    ``lax.reduce_precision``: a round trip through ``astype`` inside one
    fusion may be kept in float32 on the chip (PERF.md, PR 29 and 34)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _bf16_sum(prod_terms):
    """Sum over the first axis with a bfloat16 running sum."""
    def add(acc, term):
        return _to_bf16(acc + term), None
    out, _ = jax.lax.scan(add, jnp.zeros(prod_terms.shape[1:], F32),
                          prod_terms)
    return out


def _matmul(x, w, fault):
    """x (T, K) @ w (K, N); under ``acc_bf16`` the sum over K is carried
    in bfloat16, 128 terms at a time in float32 (what a matrix unit whose
    accumulator is bfloat16 does)."""
    if fault != "acc_bf16":
        return x @ w
    K = x.shape[1]
    step = 128 if K % 128 == 0 else K
    xs = x.reshape(x.shape[0], K // step, step).transpose(1, 0, 2)
    ws = w.reshape(K // step, step, w.shape[1])
    return _bf16_sum(jnp.einsum("jtk,jkn->jtn", xs, ws))


def _swiglu(u, w_in, w_out, fault=None):
    """``(in, out)`` matrices, gate columns first."""
    gu = _matmul(u, w_in, fault)
    F = w_in.shape[1] // 2
    return _matmul(_silu(gu[:, :F]) * gu[:, F:], w_out, fault)


@functools.partial(jax.jit, static_argnames=("fault",))
def _dense(u, w, fault):
    return _swiglu(u, _f32(w["ff_in_weight"]).T, _f32(w["ff_out_weight"]).T)


@functools.partial(jax.jit, static_argnames=("k", "offset", "held", "fault"))
def _shares(u, w_r, k, offset, held, fault):
    """(T, held): the weight each held expert has on each row."""
    logits = u @ _f32(w_r).T
    if fault == "router_bf16":
        logits = _to_bf16(logits)
    pr = jax.nn.softmax(logits, -1)
    if fault == "router_bf16":
        pr = _to_bf16(pr)
    w, idx = jax.lax.top_k(pr, k)
    ids = jnp.arange(held)[None, None, :] + offset
    mine = idx[:, :, None] == ids
    if fault == "held_norm":
        # the ten weights normalised over the picks held HERE only
        total = jnp.sum(jnp.where(mine.any(-1), w, 0.0), -1, keepdims=True)
        w = w / jnp.maximum(total, 1e-30)
    else:
        w = w / w.sum(-1, keepdims=True)
    return jnp.sum(jnp.where(mine, w[:, :, None], 0.0), 1)


@functools.partial(jax.jit, static_argnames=("k",))
def _picks(u, w_r, k):
    """(T, k) the experts each row picks, and (T,) how far its last pick's
    router logit lies above the best one not picked."""
    top, idx = jax.lax.top_k(u @ _f32(w_r).T, k + 1)
    return idx[:, :k], top[:, k - 1] - top[:, k]


def router(cfg, params, i, u, name=NAME):
    """Routed layer ``i``'s picks over normed rows ``u (T, D)`` float32:
    ``(experts (T, k), gap (T,))``, the gap between the last pick's
    logit and the next expert's: a row whose gap is smaller than what its
    input is off by may pick otherwise."""
    d = dims(cfg)
    with jax.default_matmul_precision("highest"):
        return _picks(_f32(u), params[f"{name}_l{i}_router_weight"], k=d["k"])


@functools.partial(jax.jit, static_argnames=("fault",))
def _expert(u, share, w_in, w_out, fault):
    return share[:, None] * _swiglu(u, _f32(w_in), _f32(w_out), fault)


@functools.partial(jax.jit, static_argnames=("fault",))
def _shared(u, w, fault):
    return _swiglu(u, _f32(w["shared_in_weight"]).T,
                   _f32(w["shared_out_weight"]).T, fault)


@jax.jit
def _norm(x, gamma, eps):
    return _rms(x, _f32(gamma), eps)


def ffn(cfg, params, i, u, fault=None, name=NAME):
    """Layer ``i``'s feed-forward block over normed rows ``u (T, D)``
    float32: the dense SwiGLU, or the shared expert plus ``routed_scale``
    times this share's routed part."""
    d = dims(cfg)
    pre = f"{name}_l{i}_"
    w = {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}
    with jax.default_matmul_precision("highest"):
        if d["dense"][i]:
            return _dense(u, w, fault)
        share = _shares(u, w["router_weight"], k=d["k"], offset=d["offset"],
                        held=d["held"], fault=fault)
        routed = jnp.zeros_like(u)
        for e in range(d["held"]):
            routed = routed + _expert(
                u, share[:, e], w["experts_in_weight"][e],
                w["experts_out_weight"][e], fault)
        return _shared(u, w, fault) + F32(d["scale"]) * routed


def forward(cfg, params, tokens, positions, name=NAME, fault=None,
            block=512, taps=False):
    """Float32 logits ``(len(positions), vocab)`` at the given positions
    of the full forward pass over ``tokens`` (a multiple of ``block``
    long: pad behind the real ones, a causal model does not see it).
    With ``taps``: ``(logits, {routed layer: its normed rows in front of
    the feed-forward block, at the positions})``."""
    d = dims(cfg)
    assert fault is None or fault in FAULTS, fault
    assert len(tokens) % block == 0
    eps = F32(d["eps"])
    ropes = {}
    for kind, rp in cfg["rope_parameters"].items():
        if not isinstance(rp, dict):
            continue
        dim, inv, scale = inv_freq(
            rp, d["Dh"], blend=not (fault == "no_yarn"
                                    and kind == "full_attention"))
        ropes[kind] = (dim, tuple(float(x) for x in inv), float(scale))
    window = d["window"]
    if fault == "window_496":           # 496 of 512: a thirty-second fewer
        window -= max(1, window // 32)
    tapped = {}
    with jax.default_matmul_precision("highest"):
        x = _f32(params[f"{name}_tok_embed_weight"][jnp.asarray(tokens)])
        for i, kind in enumerate(d["kinds"]):
            pre = f"{name}_l{i}_"
            w = {k[len(pre):]: v for k, v in params.items()
                 if k.startswith(pre)}
            x = x + _attention(
                _norm(x, w["ln1_gamma"], eps), w, H=d["heads"][i],
                Hkv=d["Hkv"], Dh=d["Dh"],
                window=window if kind == "sliding_attention" else 0,
                rope=ropes[kind], block=block)
            u = _norm(x, w["ln2_gamma"], eps)
            if taps and not d["dense"][i]:
                tapped[i] = u[jnp.asarray(positions)]
            x = x + ffn(cfg, params, i, u, fault, name)
        h = _norm(x[jnp.asarray(positions)], params[f"{name}_ln_f_gamma"],
                  eps)
        logits = h @ _f32(params[f"{name}_head_weight"]).T
    return (logits, tapped) if taps else logits


def teacher_force(cfg, params, prompt, generated, fault=None, block=512,
                  taps=False):
    """Teacher-force the engine's own output through the reference.
    Returns ``regrets``, at every generated position the reference's best
    logit minus its logit of the token the engine chose, and
    ``logit_std``; with ``taps`` also ``ffn_inputs``, ``{routed layer:
    its normed rows (G, D) at the generated positions}``.  The sequence is
    padded with token 0 to a multiple of ``8 * block`` positions, so that
    sequences of several lengths share few compiled passes."""
    seq = np.concatenate([np.asarray(prompt), np.asarray(generated)])[:-1]
    P, G, n = len(prompt), len(generated), len(seq)
    pad = -n % (8 * block)
    seq = np.concatenate([seq, np.zeros(pad, seq.dtype)])
    rows = -(-G // 256) * 256
    at = np.arange(P - 1, P + G - 1)
    at = np.concatenate([at, np.full(rows - G, at[-1])])
    out = forward(cfg, params, seq, at, fault=fault, block=block, taps=taps)
    lg = (out[0] if taps else out)[:G]
    chosen = jnp.take_along_axis(
        lg, jnp.asarray(np.asarray(generated))[:, None], 1)[:, 0]
    regrets = np.asarray(lg.max(-1) - chosen, np.float64)
    res = {"regrets": [float(r) for r in regrets],
           "logit_std": float(jnp.std(lg))}
    if taps:
        res["ffn_inputs"] = {i: rows[:G] for i, rows in out[1].items()}
    return res
