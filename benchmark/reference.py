"""Plain references the benchmark checks the program against.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")`` (on a TPU a float32 matmul
otherwise runs in bf16 passes): no cache, no kernels, no batching, no
bucket padding.  They share no code with ``mxnet_tpu``; they read the
program's parameter dict by the names ``mx.models.gpt`` and
``mx.models.resnet`` give their arguments.

``gpt_logits``   Mistral-style decoder: RMSNorm (eps 1e-5), grouped-query
                 causal attention with rotary embeddings (half-split
                 rotation, base 10000 — the program's base,
                 ``serve/engine.py:_rope``; the published model's is 1e6),
                 SwiGLU, untied head.  Projections carry biases (zero in
                 the benchmark's weights).
``resnet_loss``  the pre-activation ResNet-50 of ``mx.models.resnet``
                 (NHWC, space-to-depth stem), BatchNorm on batch
                 statistics (eps 2e-5), mean cross-entropy.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _f32(x):
    return jnp.asarray(x).astype(F32)


def _rms(x, gamma, eps=1e-5):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gamma


def _rope(u, base=10000.0):
    """(T, H, Dh) rotated by position: pairs (i, i + Dh/2)."""
    T, _, dh = u.shape
    half = dh // 2
    inv = base ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(T, dtype=F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    u1, u2 = u[..., :half], u[..., half:]
    return jnp.concatenate([u1 * cos - u2 * sin, u1 * sin + u2 * cos], -1)


@functools.partial(jax.jit, static_argnames=("num_heads", "kv_heads"))
def _gpt_layer(x, w, num_heads, kv_heads):
    """One decoder layer over a whole sequence x: (T, D).  ``w`` maps the
    short names (q_weight, ..., ff_down_bias, ln1_gamma, ln2_gamma) to
    this layer's arrays in whatever dtype they are served in."""
    T, D = x.shape
    dh = D // num_heads
    fc = lambda h, n: h @ _f32(w[n + "_weight"]).T + _f32(w[n + "_bias"])
    h = _rms(x, _f32(w["ln1_gamma"]))
    q = _rope(fc(h, "q").reshape(T, num_heads, dh))
    k = _rope(fc(h, "k").reshape(T, kv_heads, dh))
    v = fc(h, "v").reshape(T, kv_heads, dh)
    group = num_heads // kv_heads
    k = jnp.repeat(k, group, axis=1)              # (T, H, dh)
    v = jnp.repeat(v, group, axis=1)
    sc = jnp.einsum("qhd,shd->hqs", q, k) / jnp.sqrt(F32(dh))
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    sc = jnp.where(causal[None], sc, -jnp.inf)
    at = jnp.einsum("hqs,shd->qhd", jax.nn.softmax(sc, -1), v)
    x = x + fc(at.reshape(T, D), "proj")
    h = _rms(x, _f32(w["ln2_gamma"]))
    g = fc(h, "ff_gate")
    return x + fc(g * jax.nn.sigmoid(g) * fc(h, "ff_up"), "ff_down")


@jax.jit
def _head_slice(h, w, b):
    return h @ _f32(w).T + _f32(b)


def gpt_logits(params, tokens, positions, num_heads, kv_heads, name="gpt",
               vocab_slice=8192):
    """Float32 logits (len(positions), vocab) of the full causal forward
    pass over ``tokens`` at the given positions.  Weights are upcast one
    layer at a time and the head in vocabulary slices, so the reference
    fits beside a serving engine that fills most of the chip."""
    with jax.default_matmul_precision("highest"):
        x = _f32(params[f"{name}_tok_embed_weight"][jnp.asarray(tokens)])
        i = 0
        while f"{name}_l{i}_q_weight" in params:
            pre = f"{name}_l{i}_"
            w = {k[len(pre):]: v for k, v in params.items()
                 if k.startswith(pre)}
            x = _gpt_layer(x, w, num_heads=num_heads, kv_heads=kv_heads)
            i += 1
        h = _rms(x[jnp.asarray(positions)],
                 _f32(params[f"{name}_ln_f_gamma"]))
        hw, hb = params[f"{name}_head_weight"], params[f"{name}_head_bias"]
        out = [_head_slice(h, hw[s:s + vocab_slice], hb[s:s + vocab_slice])
               for s in range(0, hw.shape[0], vocab_slice)]
        return jnp.concatenate(out, -1)


def greedy_regret(params, prompt, generated, num_heads, kv_heads):
    """Teacher-force the engine's own output through the reference.  At
    every generated position: reference max logit minus the reference
    logit of the token the engine chose (0 when they agree, O(logit
    spread) when the engine read a wrong block, head or mask).  Returns
    the list of regrets, one per generated token."""
    seq = np.concatenate([np.asarray(prompt), np.asarray(generated)])
    P, G = len(prompt), len(generated)
    logits = gpt_logits(params, seq[:-1], np.arange(P - 1, P + G - 1),
                        num_heads, kv_heads)
    chosen = jnp.take_along_axis(
        logits, jnp.asarray(generated)[:, None], axis=1)[:, 0]
    return np.asarray(jnp.max(logits, -1) - chosen).tolist()


# -- ResNet-50 ---------------------------------------------------------------

def _bn(x, gamma, beta, eps=2e-5):
    mean = jnp.mean(x, (0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), (0, 1, 2))
    return (x - mean) * jax.lax.rsqrt(var + eps) * gamma + beta


def _conv(x, w, stride=1, pad=0):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "OIHW", "NHWC"))


def resnet_features(p, data, units=(3, 4, 6, 3)):
    """Pooled features (N, 2048) of the pre-activation bottleneck ResNet
    ``mx.models.resnet(layout="NHWC", stem="s2d")`` builds, from the
    space-to-depth input (N, H/2, W/2, 12), in training mode."""
    g = lambda n: _f32(p[n])
    bn = lambda x, n: _bn(x, g(n + "_gamma"), g(n + "_beta"))
    x = _bn(_f32(data), 1.0, g("bn_data_beta"))         # fix_gamma=True
    x = jnp.pad(x, ((0, 0), (2, 1), (2, 1), (0, 0)))
    x = jax.nn.relu(bn(_conv(x, g("conv0_weight")), "bn0"))
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), ((0, 0), (1, 1), (1, 1), (0, 0)))
    for stage, n_units in enumerate(units, start=1):
        for unit in range(1, n_units + 1):
            n = f"stage{stage}_unit{unit}"
            stride = 2 if (unit == 1 and stage > 1) else 1
            a1 = jax.nn.relu(bn(x, n + "_bn1"))
            c = _conv(a1, g(n + "_conv1_weight"))
            c = _conv(jax.nn.relu(bn(c, n + "_bn2")), g(n + "_conv2_weight"),
                      stride, 1)
            c = _conv(jax.nn.relu(bn(c, n + "_bn3")), g(n + "_conv3_weight"))
            sc = (_conv(a1, g(n + "_sc_weight"), stride) if unit == 1 else x)
            x = c + sc
    return jnp.mean(jax.nn.relu(bn(x, "bn1")), (1, 2))


def _head_loss(fc_w, fc_b, feats, labels):
    logp = jax.nn.log_softmax(feats @ fc_w.T + fc_b)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], 1))


def resnet_loss_and_head_grad(p, data, labels):
    """Mean cross-entropy of the batch in float32, and its gradient with
    respect to the classifier's weight (``fc1_weight``) by ``jax.grad``.
    The gradient stops at the classifier: float32 activations of the
    whole body at batch 256 do not fit beside the trainer."""
    with jax.default_matmul_precision("highest"):
        feats = jax.jit(resnet_features)(p, data)
        loss, grad = jax.jit(jax.value_and_grad(_head_loss))(
            _f32(p["fc1_weight"]), _f32(p["fc1_bias"]), feats,
            jnp.asarray(labels, jnp.int32))
    return float(loss), grad
