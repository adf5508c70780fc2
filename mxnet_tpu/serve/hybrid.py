"""Serving programs for hybrid decoders (``models/hybrid.py``): layers of
two kinds, and a per-request state pool beside the paged K/V cache.

ONE layer function, :func:`layer`, is ``norm -> mixer(kind) -> mlp``; the
decode, prefill and chunk builders all call it and differ only in the
*mixer context* they hand it (:class:`_DecodeMix` for one position of B
rows, :class:`_SpanMix` for a span of one request):

- an **attention** layer writes its K/V through the block table into a
  cache stacked over the ATTENTION layers only and FLAT in its minor
  axis, ``(A, blocks, bs, Hkv * Dh)`` (a ``(Hkv, Dh) = (8, 64)`` bfloat16
  tile is padded fourfold on the chip; side by side the heads pad
  nothing), and attends with an explicit score scale and no positional
  encoding: ``paged_attention(..., layer=a, scale=...)`` at decode,
  dense causal scores over a whole prompt, the gathered table view
  over a chunk;
- a **state-space** layer reads and writes the request's slot of two
  pools stacked over the STATE-SPACE layers, ``ssm (M, S+1, H, P, N)``
  float32 and ``conv (M, S+1, (K-1) * C)`` in the activation dtype (a
  slot's K-1 rows side by side, for the same reason), slot 0 the null
  slot that padded rows write to.  Decode updates the live
  rows' states in place at ``(layer, slot[b])``
  (``ops.ssm.ssm_state_update``); a span runs the chunked scan
  (``ops.ssm.ssd_chunked_scan``) from the slot's state, or from ZERO
  when the span starts at position 0: the first pass of a request (and
  of a preempted request prefilled anew) never reads its slot, so a
  recycled slot cannot leak its last owner's state.  Padded positions
  of a bucket do not move the state (``dt`` 0 there; the convolution's
  rows are taken at the last real position).

Program operands (``Engine._program_specs`` mirrors them): params, the
four caches ``ck, cv, ssm, conv`` (donated through), the host-fed
operands of the ``gpt`` program of the same kind, then the state slot
(``(B,)`` for decode, a scalar for prefill and chunk) and the tail
``serve/programs.py::_finish`` takes, the ``gpt`` programs' epilogue
(the sampling triple in sampling mode, the rng key).  A span attends
through ``ops.attention.masked_attention``, as theirs do.
"""

from __future__ import annotations

import collections

import jax
import jax.numpy as jnp
import numpy as np

from ..models.generate import _ln
from ..ops import ssm as ssm_ops
from ..ops.attention import masked_attention, paged_attention
from .kv_block_manager import STATE_POOL_NO_PREFIX
from .programs import _finish

__all__ = ["HybridCfg", "hybrid_cfg", "check_params", "refuse", "layer",
           "matmul_flops", "build_decode", "build_prefill", "build_chunk",
           "SCAN_SCOPE"]

# named scope of the chunked scan: device-trace operation names carry it
SCAN_SCOPE = "ssd_chunked_scan"

# what the compiled programs close over: the decoder's description plus
# the position of each layer in its kind's stack (-1: not of that kind)
HybridCfg = collections.namedtuple("HybridCfg", ["dec", "kv_index",
                                                 "ssm_index"])


def hybrid_cfg(dec):
    kv, sm, a, m = [], [], 0, 0
    for kind in dec.layer_types:
        kv.append(a if kind == "attention" else -1)
        sm.append(m if kind == "mamba" else -1)
        a += kind == "attention"
        m += kind == "mamba"
    return HybridCfg(dec, tuple(kv), tuple(sm))


def check_params(dec, params):
    """The parameter dict against the description: every name, every
    shape.  Returns the engine's ``spec`` (the keys a gpt() checkpoint's
    ``detect_gpt_variant`` gives)."""
    want = dec.param_shapes()
    missing = sorted(set(want) - set(params))
    if missing:
        raise ValueError(f"hybrid decoder: parameters missing: "
                         f"{missing[:4]}{' ...' if len(missing) > 4 else ''}")
    for k, shape in want.items():
        if tuple(params[k].shape) != tuple(shape):
            raise ValueError(f"hybrid decoder: {k} has shape "
                             f"{tuple(params[k].shape)}, the description "
                             f"says {tuple(shape)}")
    return {"n_layers": dec.num_layers, "d_model": dec.d_model,
            "head_dim": dec.head_dim, "kv_heads": dec.kv_heads,
            "vocab": dec.vocab_size, "pos_table": None, "swiglu": True,
            "tied": True, "rmsnorm": True}


def refuse(prefix_cache, spec_k, adapters, kv_dtype, quantize, tp,
           host_kv_bytes):
    """What a hybrid engine cannot do yet, each refused by name at
    construction (docs/how_to/serve.md, "Hybrid decoders")."""
    why = {
        "prefix_cache": (prefix_cache, STATE_POOL_NO_PREFIX),
        "spec_k": (spec_k, "speculative decoding would have to roll the "
                   "recurrent state back over rejected tokens"),
        "adapters": (adapters, "LoRA adapters are wired into the gpt() "
                     "projections only"),
        "kv_dtype": (kv_dtype, "int8 K/V is wired into the gpt() "
                     "programs only"),
        "quantize": (quantize, "weight-only int8 is wired into the "
                     "gpt() projections only"),
        "tp": (tp > 1, "the state pool and the scan have no sharding "
               "rules yet"),
        "host_kv_bytes": (host_kv_bytes, "host K/V offload parks prefix-"
                          "cache blocks, and the prefix cache is off"),
    }
    for arg, (on, reason) in why.items():
        if on:
            raise ValueError(f"a hybrid decoder cannot be served with "
                             f"{arg}: {reason}")


def matmul_flops(dec, rows, head_rows):
    """Operations of the matrix products of one pass over ``rows``
    positions that samples ``head_rows`` of them."""
    per = sum(2 * int(np.prod(s)) for k, s in dec.param_shapes().items()
              if k.endswith("_weight") and "tok_embed" not in k
              and "conv" not in k)
    return rows * per + head_rows * 2 * dec.vocab_size * dec.d_model


def _fc(x, w):
    return x @ w.T.astype(x.dtype)


def _silu_f32(x):
    xf = x.astype(jnp.float32)
    return xf * jax.nn.sigmoid(xf)


def _residual(x, y, mult):
    """x + mult * y with the sum in float32: the multiplier (0.22, say)
    has no exact bfloat16, and a residual stream scaled 0.13 % off in
    every branch is not the model."""
    return (x.astype(jnp.float32)
            + np.float32(mult) * y.astype(jnp.float32)).astype(x.dtype)


def _mamba_in(dec, params, p, h):
    """[z | xBC | dt] = W_in h."""
    di, cd = dec.d_inner, dec.conv_dim
    zxd = _fc(h, params[f"{p}_in_proj_weight"])
    return zxd[..., :di], zxd[..., di:di + cd], zxd[..., di + cd:]


def _mamba_steps(dec, params, p, dt, valid=None):
    """(dt, dA): softplus step sizes, zeroed at padded positions, and
    the log-decays ``-exp(A_log) * dt``, both float32."""
    dt = ssm_ops.softplus(dt.astype(jnp.float32)
                          + params[f"{p}_dt_bias"].astype(jnp.float32))
    if valid is not None:
        dt = jnp.where(valid[:, None], dt, np.float32(0.0))
    return dt, -jnp.exp(params[f"{p}_A_log"].astype(jnp.float32)) * dt


def _mamba_out(dec, params, p, y, z):
    """Gate, then norm over all of d_inner, then the output projection."""
    g = (y.astype(jnp.float32) * _silu_f32(z)).astype(y.dtype)
    g = _ln(g, params[f"{p}_norm_gamma"], None, eps=dec.eps)
    return _fc(g, params[f"{p}_out_proj_weight"])


def _qkv(dec, params, p, h):
    Hq, Hkv, Dh = dec.num_heads, dec.kv_heads, dec.head_dim
    T = h.shape[0]
    qkv = _fc(h, params[f"{p}_qkv_weight"])
    return (qkv[:, :Hq * Dh].reshape(T, Hq, Dh),
            qkv[:, Hq * Dh:(Hq + Hkv) * Dh].reshape(T, Hkv, Dh),
            qkv[:, (Hq + Hkv) * Dh:].reshape(T, Hkv, Dh))


def _flat(u):
    """(T, heads, Dh) -> (T, heads * Dh): a position's heads side by side,
    the K/V cache's minor axis."""
    return u.reshape(u.shape[0], -1)


class _DecodeMix:
    """One position for each of B rows: K/V through the block tables,
    states at ``(layer, slots[b])``."""

    def __init__(self, hc, params, ck, cv, ssm, conv, pos, tables, slots,
                 block_size):
        self.hc, self.params = hc, params
        self.ck, self.cv, self.ssm, self.conv = ck, cv, ssm, conv
        self.tables, self.slots = tables, slots
        self.blk = jnp.take_along_axis(
            tables, (pos // block_size)[:, None], axis=1)[:, 0]
        self.off = pos % block_size
        self.ctx = pos + 1

    def attention(self, i, h):
        dec, a = self.hc.dec, self.hc.kv_index[i]
        p = f"{dec.name}_l{i}"
        q, k, v = _qkv(dec, self.params, p, h)
        self.ck = self.ck.at[a, self.blk, self.off].set(_flat(k))
        self.cv = self.cv.at[a, self.blk, self.off].set(_flat(v))
        at = paged_attention(q, self.ck, self.cv, self.tables, self.ctx,
                             layer=a, scale=dec.attention_multiplier,
                             flat_heads=dec.kv_heads)
        return _fc(at.reshape(h.shape[0], -1),
                   self.params[f"{p}_proj_weight"])

    def mamba(self, i, h):
        dec, m = self.hc.dec, self.hc.ssm_index[i]
        P, p = self.params, f"{dec.name}_l{i}"
        B = h.shape[0]
        z, xBC, dt = _mamba_in(dec, P, p, h)
        # the convolution over the slot's K-1 rows and this position
        # each row is a span of one position in front of its slot's rows
        prev = self.conv[m, self.slots].reshape(B, dec.mamba_conv - 1, -1)
        xBC, rows = jax.vmap(ssm_ops.causal_conv, in_axes=(0, 0, None, None))(
            xBC[:, None, :], prev.astype(h.dtype), P[f"{p}_conv_weight"],
            P[f"{p}_conv_bias"])
        self.conv = self.conv.at[m, self.slots].set(
            rows.astype(self.conv.dtype).reshape(B, -1))
        xBC = xBC[:, 0]
        di, N = dec.d_inner, dec.mamba_state
        x = xBC[:, :di].reshape(B, dec.mamba_heads, dec.mamba_head_dim)
        dt, dA = _mamba_steps(dec, P, p, dt)
        y, self.ssm = ssm_ops.ssm_state_update(
            self.ssm, m, self.slots, x, dt, dA, xBC[:, di:di + N],
            xBC[:, di + N:], P[f"{p}_D"])
        return _mamba_out(dec, P, p, y.reshape(B, di), z)


class _SpanMix:
    """A span of ONE request: rows hold positions [start, start +
    n_valid), rows past n_valid are padding.  ``table`` None: a whole
    prompt from position 0 (dense causal attention inside the span, the
    state from zero); else a chunk that attends through the table and
    carries the slot's state."""

    def __init__(self, hc, params, ck, cv, ssm, conv, T, start, n_valid,
                 slot, blk, off, table):
        self.hc, self.params = hc, params
        self.ck, self.cv, self.ssm, self.conv = ck, cv, ssm, conv
        self.T, self.start, self.slot = T, start, slot
        self.n_valid, self.blk, self.off, self.table = n_valid, blk, off, table
        self.valid = jnp.arange(T, dtype=jnp.int32) < n_valid

    def attention(self, i, h):
        dec, a = self.hc.dec, self.hc.kv_index[i]
        p = f"{dec.name}_l{i}"
        Hkv, Dh = dec.kv_heads, dec.head_dim
        q, k, v = _qkv(dec, self.params, p, h)
        self.ck = self.ck.at[a, self.blk, self.off].set(_flat(k))
        self.cv = self.cv.at[a, self.blk, self.off].set(_flat(v))
        if self.table is not None:
            # one gather over the stack; ck[a][table] would first copy
            # the layer's whole pool
            k = self.ck[a, self.table].reshape(-1, Hkv, Dh)
            v = self.cv[a, self.table].reshape(-1, Hkv, Dh)
        at = masked_attention(q, k, v, self.start,
                              np.float32(dec.attention_multiplier),
                              n_valid=self.n_valid)
        return _fc(at.reshape(self.T, -1), self.params[f"{p}_proj_weight"])

    def mamba(self, i, h):
        dec, m = self.hc.dec, self.hc.ssm_index[i]
        P, p = self.params, f"{dec.name}_l{i}"
        T, di, N = self.T, dec.d_inner, dec.mamba_state
        H, Pd = dec.mamba_heads, dec.mamba_head_dim
        z, xBC, dt = _mamba_in(dec, P, p, h)
        if self.table is None:
            prev = jnp.zeros((dec.mamba_conv - 1, dec.conv_dim), h.dtype)
            S0 = jnp.zeros((H, Pd, N), jnp.float32)
        else:
            # a pass that starts at position 0 starts from zero whatever
            # the slot holds: admission and re-prefill reset it here
            fresh = self.start == 0
            prev = jnp.where(fresh, jnp.zeros((), h.dtype),
                             self.conv[m, self.slot].astype(h.dtype)
                             ).reshape(dec.mamba_conv - 1, dec.conv_dim)
            S0 = jnp.where(fresh, np.float32(0.0), self.ssm[m, self.slot])
        xBC, rows = ssm_ops.causal_conv(
            xBC, prev, P[f"{p}_conv_weight"], P[f"{p}_conv_bias"],
            n_valid=self.n_valid)
        self.conv = self.conv.at[m, self.slot].set(
            rows.astype(self.conv.dtype).reshape(-1))
        dt, dA = _mamba_steps(dec, P, p, dt, self.valid)
        with jax.named_scope(SCAN_SCOPE):
            y, S = ssm_ops.ssd_chunked_scan(
                xBC[:, :di].reshape(T, H, Pd), dt, dA, xBC[:, di:di + N],
                xBC[:, di + N:], P[f"{p}_D"], S0, dec.mamba_chunk)
        self.ssm = self.ssm.at[m, self.slot].set(S)
        return _mamba_out(dec, P, p, y.reshape(T, di), z)


def layer(hc, params, i, x, mix):
    """THE layer of a hybrid decoder: norm -> mixer(kind) -> mlp, each
    branch scaled by the residual multiplier.  ``mix`` carries the
    caches and the pass's operands and answers ``attention(i, h)`` /
    ``mamba(i, h)``."""
    dec = hc.dec
    p = f"{dec.name}_l{i}"
    h = _ln(x, params[f"{p}_ln1_gamma"], None, eps=dec.eps)
    if dec.layer_types[i] == "attention":
        y = mix.attention(i, h)
    else:
        y = mix.mamba(i, h)
    x = _residual(x, y, dec.residual_multiplier)
    h = _ln(x, params[f"{p}_ln2_gamma"], None, eps=dec.eps)
    gu = _fc(h, params[f"{p}_ff_in_weight"])
    act = (_silu_f32(gu[..., :dec.d_ff]).astype(gu.dtype)
           * gu[..., dec.d_ff:])
    return _residual(x, _fc(act, params[f"{p}_ff_out_weight"]),
                     dec.residual_multiplier)


def _embed(dec, params, toks):
    e = params[f"{dec.name}_tok_embed_weight"][toks]
    return (e.astype(jnp.float32)
            * np.float32(dec.embedding_multiplier)).astype(e.dtype)


def _logits(dec, params, x):
    h = _ln(x, params[f"{dec.name}_ln_f_gamma"], None, eps=dec.eps)
    lg = h @ params[f"{dec.name}_tok_embed_weight"].T.astype(h.dtype)
    return (lg.astype(jnp.float32)
            / np.float32(dec.logits_scaling)).astype(lg.dtype)


def _stack(hc, params, x, mix):
    for i in range(hc.dec.num_layers):
        x = layer(hc, params, i, x, mix)
    return x, (mix.ck, mix.cv, mix.ssm, mix.conv)


def _jit(fn, donate):
    # params, then the four caches
    return jax.jit(fn, donate_argnums=(1, 2, 3, 4) if donate else ())


def build_decode(cfg, donate):
    hc = cfg.hybrid

    def decode(params, ck, cv, ssm, conv, toks, pos, tables, slots, *tail):
        mix = _DecodeMix(hc, params, ck, cv, ssm, conv, pos, tables, slots,
                         cfg.block_size)
        x, caches = _stack(hc, params, _embed(hc.dec, params, toks), mix)
        return _finish(cfg, _logits(hc.dec, params, x), caches, tail,
                       scalar=False)

    return _jit(decode, donate)


def build_prefill(cfg, P, donate):
    hc = cfg.hybrid

    def prefill(params, ck, cv, ssm, conv, toks, plen, blk, off, slot,
                *tail):
        """Whole prompt at padded length P for ONE request, from
        position 0: the slot's state is written, never read."""
        mix = _SpanMix(hc, params, ck, cv, ssm, conv, P, 0, plen, slot,
                       blk, off, None)
        x, caches = _stack(hc, params, _embed(hc.dec, params, toks), mix)
        return _finish(cfg, _logits(hc.dec, params, x[plen - 1][None]),
                       caches, tail, scalar=True)

    return _jit(prefill, donate)


def build_chunk(cfg, C, donate):
    hc = cfg.hybrid

    def chunk(params, ck, cv, ssm, conv, toks, start, n_valid, table, blk,
              off, slot, *tail):
        """C rows of ONE request at positions [start, start + n_valid):
        attends through the table, starts from the slot's state (from
        zero at start 0) and writes it back."""
        mix = _SpanMix(hc, params, ck, cv, ssm, conv, C, start, n_valid,
                       slot, blk, off, table)
        x, caches = _stack(hc, params, _embed(hc.dec, params, toks), mix)
        return _finish(cfg, _logits(hc.dec, params, x[n_valid - 1][None]),
                       caches, tail, scalar=True)

    return _jit(chunk, donate)
