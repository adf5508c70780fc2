"""The plain reference of a one-branch decoder (every layer a Mamba-2
mixer in state groups, an attention without positions, or routed experts
in a latent width), the benchmark's own copy: it shares no code with
``mxnet_tpu`` and reads the program's parameter dict by the names
``mx.models.branch_decoder`` gives its parameters.  ``cfg`` is the
configuration file's dictionary (the source's keys).

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no cache, no chunked scan, no
kernel, no bucket padding, no sorting of picks; the recurrence runs token
by token and every held expert runs on every row.  RMSNorm eps 1e-5, no
bias on any linear map, untied head:

    h_i = h_{i-1} + Block_i(RMSNorm_i(h_{i-1}))      Block_i by pattern[i]
    logits = RMSNorm_f(h_L) W_head^T

    M  [z | xBC | dt] = W_in u (rows 8192 | 10240 | 128);
       xBC = silu(conv4(xBC) + b); x = xBC[:8192], B, C = the next 2 x
       (G, N); dt = softplus(dt + dt_bias); head h reads group h // (H/G);
       S_t[h] = exp(-exp(A_log[h]) dt_t[h]) S_{t-1}[h] + dt_t[h] x_t[h] (x) B_t[g]
       y_t[h] = S_t[h] C_t[g] + D[h] x_t[h]
       out = W_out GroupRMSNorm_G(y * silu(z))      (gate, then each group's RMS)
    *  32 query / 2 key-value heads of 128, causal, q k / sqrt(128), NO
       positional encoding (the family's modelling code reads rope_theta
       in no layer: ``assumed`` in the configuration's file)
    E  s = sigmoid(u W_r) over all n_routed_experts, float32; the 22
       largest of s + b (b the selection bias); w_e = 5 s_e / sum_picked s;
       l = W_down u; r = sum_{e picked AND held} w_e W2_e relu(W1_e l)^2;
       Block(u) = W_up r + Ws2 relu(Ws1 u)^2
       Only experts [expert_offset, expert_offset + num_experts_held) are
       here: a pick of another adds nothing, and W_up is applied to the
       partial sum.

So that 3.6 k positions at full width fit on the chip, weights are upcast
a layer (an expert) at a time, every sequence is padded to one length (a
causal model's earlier positions do not see the padding and the
recurrence stands still on it), attention takes its queries a block at a
time, and the head runs on the generated positions only.

``fault`` (``branch_controls.py``) computes a deliberately different
model; ``None`` is the configuration's.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
NAME = "branch"
FAULTS = ("one_group", "norm_whole", "kv_swapped", "relu_plain", "scale_1",
          "no_bias", "acc_bf16", "state_bf16")
KINDS = {"M": "mamba", "*": "attention", "E": "moe"}


def _f32(x):
    return jnp.asarray(x).astype(F32)


def _rms(x, gamma, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gamma


def _silu(x):
    return x * jax.nn.sigmoid(x)


def dims(cfg):
    """The sizes the equations need, from the source's keys."""
    L = cfg["num_hidden_layers"]
    pattern = cfg["hybrid_override_pattern"]
    assert len(pattern) == L and set(pattern) <= set(KINDS), pattern
    assert not (cfg["attention_bias"] or cfg["mlp_bias"] or cfg["use_bias"]
                or cfg["mamba_proj_bias"] or cfg["tie_word_embeddings"])
    assert cfg["use_conv_bias"] and cfg["norm_topk_prob"]
    assert cfg["mlp_hidden_act"] == "relu2" and cfg["n_shared_experts"] == 1
    assert cfg["n_group"] == 1 and cfg["topk_group"] == 1   # the router's
    assert cfg["num_nextn_predict_layers"] == 0             # MTP not loaded
    H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    assert H * P == cfg["expand"] * cfg["hidden_size"]
    assert H % cfg["n_groups"] == 0
    return {"D": cfg["hidden_size"], "V": cfg["vocab_size"], "L": L,
            "kinds": tuple(KINDS[c] for c in pattern),
            "Hq": cfg["num_attention_heads"],
            "Hkv": cfg["num_key_value_heads"], "Dh": cfg["head_dim"],
            "H": H, "P": P, "N": cfg["ssm_state_size"],
            "G": cfg["n_groups"], "K": cfg["conv_kernel"],
            "chunk": cfg["chunk_size"],
            "E": cfg["n_routed_experts"], "k": cfg["num_experts_per_tok"],
            "F": cfg["moe_intermediate_size"],
            "latent": cfg["moe_latent_size"],
            "Fs": cfg["moe_shared_expert_intermediate_size"],
            "scale": cfg["routed_scaling_factor"],
            "offset": cfg["expert_offset"], "held": cfg["num_experts_held"],
            "eps": cfg["layer_norm_epsilon"]}


def _to_bf16(x):
    """Round float32 values to what bfloat16 holds, and stay float32.  By
    ``lax.reduce_precision``: a round trip through ``astype`` inside one
    fusion may be kept in float32 on the chip (PERF.md, PR 29 and 34)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


# -- the mixers ---------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("Hq", "Hkv", "Dh", "block",
                                             "swap_kv"))
def _attention(u, w, Hq, Hkv, Dh, block, swap_kv=False):
    """Causal grouped-query attention over rows ``u (T, D)``, no
    positions; the queries a ``block`` at a time (T a multiple of it).
    ``swap_kv``: the groups of query heads read the kv heads in reverse
    order (the fault ``kv_swapped``)."""
    T = u.shape[0]
    qkv = u @ _f32(w["qkv_weight"]).T
    q = qkv[:, :Hq * Dh].reshape(T // block, block, Hkv, Hq // Hkv, Dh)
    k = qkv[:, Hq * Dh:(Hq + Hkv) * Dh].reshape(T, Hkv, Dh)
    v = qkv[:, (Hq + Hkv) * Dh:].reshape(T, Hkv, Dh)
    if swap_kv:
        k, v = k[:, ::-1], v[:, ::-1]

    def rows(args):
        i, qb = args
        s = jnp.einsum("qkgd,skd->kgqs", qb, k) * F32(1.0 / math.sqrt(Dh))
        t = i * block + jnp.arange(block)[:, None]
        s = jnp.where((jnp.arange(T)[None, :] <= t)[None, None], s, -jnp.inf)
        return jnp.einsum("kgqs,skd->qkgd", jax.nn.softmax(s, -1), v)

    at = jax.lax.map(rows, (jnp.arange(T // block), q))
    return at.reshape(T, Hq * Dh) @ _f32(w["proj_weight"]).T


@functools.partial(jax.jit, static_argnames=("H", "P", "N", "G", "K", "eps",
                                             "fault"))
def _mamba(u, w, n, H, P, N, G, K, eps, fault):
    """(the layer's output (T, D), the states after position ``n`` - 1)."""
    T = u.shape[0]
    di, cd = H * P, H * P + 2 * G * N
    zxd = u @ _f32(w["in_proj_weight"]).T
    z, xBC, dt = zxd[:, :di], zxd[:, di:di + cd], zxd[:, di + cd:]
    pad = jnp.concatenate([jnp.zeros((K - 1, cd), F32), xBC], 0)
    cw = _f32(w["conv_weight"])
    conv = _f32(w["conv_bias"])[None, :]
    for j in range(K):
        conv = conv + pad[j:j + T] * cw[None, :, j]
    xBC = _silu(conv)
    x = xBC[:, :di].reshape(T, H, P)
    Bg = xBC[:, di:di + G * N].reshape(T, G, N)
    Cg = xBC[:, di + G * N:].reshape(T, G, N)
    if fault == "one_group":            # every head reads the first group
        Bg, Cg = Bg[:, :1], Cg[:, :1]
    # every head its own group's rows
    Bm = jnp.repeat(Bg, H // Bg.shape[1], axis=1)             # (T, H, N)
    Cm = jnp.repeat(Cg, H // Cg.shape[1], axis=1)
    dt = jax.nn.softplus(dt + _f32(w["dt_bias"])[None, :])
    a = jnp.exp(-jnp.exp(_f32(w["A_log"]))[None, :] * dt)
    D = _f32(w["D"])
    real = jnp.arange(T) < n

    def step(S, inp):
        x_t, B_t, C_t, dt_t, a_t, real_t = inp
        S_t = (a_t[:, None, None] * S
               + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :])
        if fault == "state_bf16":       # what a pool kept in bfloat16 holds
            S_t = _to_bf16(S_t)
        y_t = jnp.sum(S_t * C_t[:, None, :], -1) + D[:, None] * x_t
        return jnp.where(real_t, S_t, S), y_t      # padding: S stands still

    S, y = jax.lax.scan(step, jnp.zeros((H, P, N), F32),
                        (x, Bm, Cm, dt, a, real))
    y = y.reshape(T, di) * _silu(z)
    gamma = _f32(w["norm_gamma"])
    if fault == "norm_whole":           # one RMS over all of d_inner
        y = _rms(y, gamma, eps)
    else:
        y = _rms(y.reshape(T, G, di // G), gamma.reshape(G, di // G),
                 eps).reshape(T, di)
    return y @ _f32(w["out_proj_weight"]).T, S


# -- the routed block ---------------------------------------------------------------

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


def _relu2(x, fault):
    r = jnp.maximum(x, 0.0)
    return r if fault == "relu_plain" else r * r


@functools.partial(jax.jit, static_argnames=("k", "fault"))
def _score(u, w_r, bias, k, fault):
    """(s (T, E) each expert's sigmoid, sel (T, E) what is picked by, the
    (T,) gap between the last pick's selection score and the next's)."""
    s = jax.nn.sigmoid(u @ _f32(w_r).T)
    sel = s if fault == "no_bias" else s + _f32(bias)[None, :]
    top, idx = jax.lax.top_k(sel, k + 1)
    return s, idx[:, :k], top[:, k - 1] - top[:, k]


def router(cfg, params, i, u, fault=None, name=NAME):
    """Routed layer ``i``'s picks over normed rows ``u (T, D)`` float32:
    ``(experts (T, k), gap (T,))``, the gap between the last pick's
    selection score and the next expert's: a row whose gap is smaller than
    what its input is off by may pick otherwise."""
    d = dims(cfg)
    p = f"{name}_l{i}_"
    with jax.default_matmul_precision("highest"):
        _, idx, gap = _score(_f32(u), params[p + "router_weight"],
                             params[p + "router_bias"], k=d["k"],
                             fault=fault)
    return idx, gap


@functools.partial(jax.jit, static_argnames=("offset", "held", "scale"))
def _shares(s, idx, offset, held, scale):
    """(T, held): the weight each held expert has on each row: its own
    sigmoid over the sum of ALL the row's picks', times the scale."""
    w = jnp.take_along_axis(s, idx, -1)
    w = F32(scale) * w / w.sum(-1, keepdims=True)
    ids = jnp.arange(held)[None, None, :] + offset
    return jnp.sum(jnp.where(idx[:, :, None] == ids, w[:, :, None], 0.0), 1)


@functools.partial(jax.jit, static_argnames=("fault",))
def _expert(lat, share, w_in, w_out, fault):
    hid = _relu2(_matmul(lat, _f32(w_in), fault), fault)
    return share[:, None] * _matmul(hid, _f32(w_out), fault)


@functools.partial(jax.jit, static_argnames=("fault",))
def _shared(u, w, fault):
    hid = _relu2(_matmul(u, _f32(w["shared_in_weight"]).T, fault), fault)
    return _matmul(hid, _f32(w["shared_out_weight"]).T, fault)


@jax.jit
def _project(x, w):
    return x @ _f32(w).T


@jax.jit
def _norm(x, gamma, eps):
    return _rms(x, _f32(gamma), eps)


def _layer(params, i, name):
    pre = f"{name}_l{i}_"
    return {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}


def ffn(cfg, params, i, u, fault=None, name=NAME):
    """Routed layer ``i``'s block over normed rows ``u (T, D)`` float32:
    the shared expert plus this share's routed part, projected up from
    the latent."""
    d = dims(cfg)
    w = _layer(params, i, name)
    with jax.default_matmul_precision("highest"):
        s, idx, _ = _score(u, w["router_weight"], w["router_bias"],
                           k=d["k"], fault=fault)
        share = _shares(s, idx, offset=d["offset"], held=d["held"],
                        scale=1.0 if fault == "scale_1" else d["scale"])
        lat = _project(u, w["latent_down_weight"])
        r = jnp.zeros_like(lat)
        for e in range(d["held"]):
            r = r + _expert(lat, share[:, e], w["experts_in_weight"][e],
                            w["experts_out_weight"][e], fault)
        return _shared(u, w, fault) + _project(r, w["latent_up_weight"])


# -- the pass -------------------------------------------------------------------------

def forward(cfg, params, tokens, positions, n=None, name=NAME, fault=None,
            block=256, taps=False):
    """The full forward pass over ``tokens`` (a multiple of ``block``
    long), of which the first ``n`` are real (default: all).  Returns
    ``(logits, states, tapped)``: float32 logits ``(len(positions),
    vocab)`` at the given positions; the state-space layers' states after
    position ``n`` - 1, stacked ``(M, H, P, N)``; with ``taps`` ``{routed
    layer: its normed rows at the positions}`` (else empty)."""
    d = dims(cfg)
    assert fault is None or fault in FAULTS, fault
    assert len(tokens) % block == 0
    eps = F32(d["eps"])
    n = jnp.int32(len(tokens) if n is None else n)
    at = jnp.asarray(positions)
    states, tapped = [], {}
    with jax.default_matmul_precision("highest"):
        x = _f32(params[f"{name}_tok_embed_weight"][jnp.asarray(tokens)])
        for i, kind in enumerate(d["kinds"]):
            w = _layer(params, i, name)
            if kind == "moe":
                u = _norm(x, w["ln2_gamma"], eps)
                if taps:
                    tapped[i] = u[at]
                x = x + ffn(cfg, params, i, u, fault, name)
                continue
            u = _norm(x, w["ln1_gamma"], eps)
            if kind == "attention":
                x = x + _attention(u, w, Hq=d["Hq"], Hkv=d["Hkv"],
                                   Dh=d["Dh"], block=block,
                                   swap_kv=fault == "kv_swapped")
            else:
                y, S = _mamba(u, w, n, H=d["H"], P=d["P"], N=d["N"],
                              G=d["G"], K=d["K"], eps=d["eps"], fault=fault)
                states.append(S)
                x = x + y
        h = _norm(x[at], params[f"{name}_ln_f_gamma"], eps)
        logits = _project(h, params[f"{name}_head_weight"])
    return logits, jnp.stack(states), tapped


def teacher_force(cfg, params, prompt, generated, fault=None, pad_to=None,
                  rows=None, block=256, taps=False):
    """Teacher-force the engine's own output through the reference.
    Returns ``regrets``, at every generated position the reference's best
    logit minus its logit of the token the engine chose; ``logit_std``;
    ``states`` ``(M, H, P, N)`` after prompt + generated[:-1], what the
    engine's slot holds when it has sampled the last of ``generated`` and
    not yet fed it; with ``taps`` ``ffn_inputs``, ``{routed layer: its
    normed rows (G, D) at the generated positions}``.  ``pad_to`` pads the
    sequence with token 0 (then up to a multiple of ``block``) and
    ``rows`` the generated positions by repeating the last, so that
    sequences of several lengths share one compiled pass."""
    seq = np.concatenate([np.asarray(prompt), np.asarray(generated)])[:-1]
    P, G, n = len(prompt), len(generated), len(seq)
    size = max(n, pad_to or 0)
    size += -size % block
    seq = np.concatenate([seq, np.zeros(size - n, seq.dtype)])
    at = np.arange(P - 1, P + G - 1)
    if rows is not None and rows > G:
        at = np.concatenate([at, np.full(rows - G, at[-1])])
    lg, states, tapped = forward(cfg, params, seq, at, n=n, fault=fault,
                                 block=block, taps=taps)
    lg = lg[:G]
    chosen = jnp.take_along_axis(
        lg, jnp.asarray(np.asarray(generated))[:, None], 1)[:, 0]
    regrets = np.asarray(lg.max(-1) - chosen, np.float64)
    return {"regrets": [float(r) for r in regrets],
            "logit_std": float(jnp.std(lg)), "states": states,
            "ffn_inputs": {i: r[:G] for i, r in tapped.items()}}
