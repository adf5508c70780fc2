"""The plain reference of a hybrid decoder (Mamba-2 and attention layers),
the benchmark's own copy: it shares no code with ``mxnet_tpu`` and reads
the program's parameter dict by the names ``mx.models.hybrid_decoder``
gives its parameters.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no cache, no chunked scan, no
kernels, no bucket padding; the state-space recurrence runs token by token
(``lax.scan`` over the positions), exactly as written:

    h_0 = emb_mult * E[token]
    h   = h + res_mult * Mixer_l(RMSNorm(h));  h = h + res_mult * MLP(RMSNorm(h))
    logits = RMSNorm(h_L) E^T / logits_scaling

    attention: q k^T * attention_multiplier, causal, NO positional encoding
    mamba:     [z | xBC | dt] = W_in x;  xBC = silu(conv4(xBC) + b)
               dt = softplus(dt + dt_bias);  a = exp(-exp(A_log) dt)
               S_t = a_t S_{t-1} + dt_t x_t (x) B_t;  y_t = S_t C_t + D x_t
               out = W_out RMSNorm_w(y * silu(z))

So that a thousand positions at full width fit on the chip, the pass is
teacher-forced in blocks: weights are upcast one layer at a time, every
sequence is padded to one length (a causal model's earlier positions do
not see the padding, and the recurrence stands still on it; one compiled
layer serves every check sequence), and the head runs in vocabulary
slices.  ``cfg`` is the configuration file's dictionary (the published
keys).

Besides the logits the pass gives what a sequence carries: every
state-space layer's state ``S`` after the last real position, which
``hybrid_cell`` sets beside the engine's slot.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
NAME = "hybrid"


def _f32(x):
    return jnp.asarray(x).astype(F32)


def _rms(x, gamma, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gamma


def _silu(x):
    return x * jax.nn.sigmoid(x)


def dims(cfg):
    """The sizes the equations need, from the published keys."""
    H, P, N = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    assert H * P == cfg["mamba_expand"] * cfg["hidden_size"]
    assert cfg["mamba_n_groups"] == 1 and cfg["num_local_experts"] == 0
    assert cfg["position_embedding_type"] == "nope"
    return {"D": cfg["hidden_size"], "V": cfg["vocab_size"],
            "Hq": cfg["num_attention_heads"],
            "Hkv": cfg["num_key_value_heads"],
            "Dh": cfg["hidden_size"] // cfg["num_attention_heads"],
            "F": cfg["shared_intermediate_size"], "H": H, "P": P, "N": N,
            "K": cfg["mamba_d_conv"], "eps": cfg["rms_norm_eps"],
            "layer_types": tuple(cfg["layer_types"])}


@functools.partial(jax.jit, static_argnames=("Hq", "Hkv", "Dh", "scale"))
def _attention(h, w, Hq, Hkv, Dh, scale):
    T = h.shape[0]
    qkv = h @ _f32(w["qkv_weight"]).T
    q = qkv[:, :Hq * Dh].reshape(T, Hq, Dh)
    k = qkv[:, Hq * Dh:(Hq + Hkv) * Dh].reshape(T, Hkv, Dh)
    v = qkv[:, (Hq + Hkv) * Dh:].reshape(T, Hkv, Dh)
    k = jnp.repeat(k, Hq // Hkv, axis=1)
    v = jnp.repeat(v, Hq // Hkv, axis=1)
    sc = jnp.einsum("qhd,shd->hqs", q, k) * F32(scale)
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    sc = jnp.where(causal[None], sc, -jnp.inf)
    at = jnp.einsum("hqs,shd->qhd", jax.nn.softmax(sc, -1), v)
    return at.reshape(T, Hq * Dh) @ _f32(w["proj_weight"]).T


@functools.partial(jax.jit, static_argnames=("H", "P", "N", "K", "eps"))
def _mamba(h, w, n, H, P, N, K, eps):
    """(the layer's output (T, D), the state after position ``n`` - 1)."""
    T = h.shape[0]
    di, cd = H * P, H * P + 2 * N
    zxd = h @ _f32(w["in_proj_weight"]).T
    z, xBC, dt = zxd[:, :di], zxd[:, di:di + cd], zxd[:, di + cd:]
    pad = jnp.concatenate([jnp.zeros((K - 1, cd), F32), xBC], 0)
    cw = _f32(w["conv_weight"])
    conv = _f32(w["conv_bias"])[None, :]
    for j in range(K):
        conv = conv + pad[j:j + T] * cw[None, :, j]
    xBC = _silu(conv)
    x = xBC[:, :di].reshape(T, H, P)
    Bm, Cm = xBC[:, di:di + N], xBC[:, di + N:]
    dt = jax.nn.softplus(dt + _f32(w["dt_bias"])[None, :])
    a = jnp.exp(-jnp.exp(_f32(w["A_log"]))[None, :] * dt)
    D = _f32(w["D"])

    real = jnp.arange(T) < n

    def step(S, inp):
        x_t, B_t, C_t, dt_t, a_t, real_t = inp
        S_t = (a_t[:, None, None] * S
               + (dt_t[:, None] * x_t)[:, :, None] * B_t[None, None, :])
        y_t = jnp.sum(S_t * C_t[None, None, :], -1) + D[:, None] * x_t
        return jnp.where(real_t, S_t, S), y_t      # padding: S stands still

    S, y = jax.lax.scan(step, jnp.zeros((H, P, N), F32),
                        (x, Bm, Cm, dt, a, real))
    y = _rms(y.reshape(T, di) * _silu(z), _f32(w["norm_gamma"]), eps)
    return y @ _f32(w["out_proj_weight"]).T, S


@functools.partial(jax.jit, static_argnames=("F", "eps", "rm"))
def _mlp(x, y, w, F, eps, rm):
    """The mixer's branch added, then the SwiGLU block."""
    x = x + F32(rm) * y
    gu = _rms(x, _f32(w["ln2_gamma"]), eps) @ _f32(w["ff_in_weight"]).T
    return x + F32(rm) * ((_silu(gu[:, :F]) * gu[:, F:])
                          @ _f32(w["ff_out_weight"]).T)


@jax.jit
def _head_slice(h, w):
    return h @ _f32(w).T


def forward(cfg, params, tokens, positions, n=None, name=NAME,
            vocab_slice=8192):
    """The full forward pass over ``tokens``, of which the first ``n``
    are real (default: all).  Returns (float32 logits (len(positions),
    vocab) at the given positions; the state-space layers' states after
    position ``n`` - 1, stacked (M, H, P, N))."""
    d = dims(cfg)
    rm, eps = cfg["residual_multiplier"], d["eps"]
    n = jnp.int32(len(tokens) if n is None else n)
    states = []
    with jax.default_matmul_precision("highest"):
        E = params[f"{name}_tok_embed_weight"]
        x = F32(cfg["embedding_multiplier"]) * _f32(E[jnp.asarray(tokens)])
        for i, kind in enumerate(d["layer_types"]):
            pre = f"{name}_l{i}_"
            w = {k[len(pre):]: v for k, v in params.items()
                 if k.startswith(pre)}
            h = _rms(x, _f32(w["ln1_gamma"]), eps)
            if kind == "attention":
                y = _attention(h, w, Hq=d["Hq"], Hkv=d["Hkv"], Dh=d["Dh"],
                               scale=cfg["attention_multiplier"])
            else:
                y, S = _mamba(h, w, n, H=d["H"], P=d["P"], N=d["N"],
                              K=d["K"], eps=eps)
                states.append(S)
            x = _mlp(x, y, w, F=d["F"], eps=eps, rm=rm)
        h = _rms(x[jnp.asarray(positions)],
                 _f32(params[f"{name}_ln_f_gamma"]), eps)
        out = [_head_slice(h, E[s:s + vocab_slice])
               for s in range(0, E.shape[0], vocab_slice)]
        return (jnp.concatenate(out, -1) / F32(cfg["logits_scaling"]),
                jnp.stack(states))


def logits(cfg, params, tokens, positions, **kw):
    return forward(cfg, params, tokens, positions, **kw)[0]


def teacher_force(cfg, params, prompt, generated, pad_to=None, rows=None):
    """Teacher-force the engine's own output through the reference.
    Returns a dictionary: ``regrets``, at every generated position the
    reference's best logit minus its logit of the token the engine
    chose; ``logit_std``, the standard deviation of the reference's
    logits at those positions; ``states`` (M, H, P, N) after prompt +
    generated[:-1], what the engine's slot holds when it has sampled the
    last of ``generated`` and not yet fed it.
    ``pad_to`` pads the sequence with token 0 and ``rows`` the generated
    positions by repeating the last, so that sequences of several
    lengths share one compiled pass."""
    seq = np.concatenate([np.asarray(prompt), np.asarray(generated)])[:-1]
    P, G, n = len(prompt), len(generated), len(seq)
    if pad_to is not None and pad_to > n:
        seq = np.concatenate([seq, np.zeros(pad_to - n, seq.dtype)])
    at = np.arange(P - 1, P + G - 1)
    if rows is not None and rows > G:
        at = np.concatenate([at, np.full(rows - G, at[-1])])
    lg, states = forward(cfg, params, seq, at, n=n)
    lg = lg[:G]
    chosen = jnp.take_along_axis(
        lg, jnp.asarray(np.asarray(generated))[:, None], 1)[:, 0]
    regrets = np.asarray(lg.max(-1) - chosen, np.float64)
    return {"regrets": [float(r) for r in regrets],
            "logit_std": float(jnp.std(lg)), "states": states}
