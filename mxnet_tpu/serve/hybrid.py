"""Serving programs for DESCRIBED decoders, whose layers are not all of
one kind: hybrid decoders (``models/hybrid.py``: state-space and
attention layers, a per-request state pool beside the paged K/V cache),
routed-expert decoders (``models/moe.py``: global and window
attention layers in two cache GROUPS, a dense or a routed feed-forward
block per layer) and one-branch decoders (``models/branch.py``: every
layer a mixer OR a feed-forward part, state-space layers in state
groups, routed experts in a latent width).

ONE layer function, :func:`layer`, runs the branches the description
gives the layer, ``norm -> mixer(kind)`` and ``norm -> ffn(kind)``, each
added to the residual stream (either may be ``"none"``); the decode,
prefill and chunk builders all call it and
differ only in the *mixer context* they hand it (:class:`_DecodeMix` for
one position of B rows, :class:`_SpanMix` for a span of one request):

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
  slot that padded rows write to.  Its ``B`` and ``C`` rows come in
  ``mamba_groups`` state groups, each read by its own heads and normed
  on its own behind the gate.  Decode updates the live
  rows' states in place at ``(layer, slot[b])``
  (``ops.ssm.ssm_state_update``); a span runs the chunked scan
  (``ops.ssm.ssd_chunked_scan``) from the slot's state, or from ZERO
  when the span starts at position 0: the first pass of a request (and
  of a preempted request prefilled anew) never reads its slot, so a
  recycled slot cannot leak its last owner's state.  Padded positions
  of a bucket do not move the state (``dt`` 0 there; the convolution's
  rows are taken at the last real position);
- a **gated attention** layer (``full_attention`` / ``sliding_attention``)
  has its own count of query heads, its kind's rotary scheme and a
  per-head sigmoid gate, and reads and writes its GROUP's cache: the
  global group ``(F, blocks, bs, Hkv, Dh)`` stacked over the full
  layers, or the window group ``(W, blocks_w, bs, Hkv, Dh)`` stacked
  over the window layers, each through its own block table (the window
  group's table holds the null block behind the window: those blocks
  went back to their free list, ``kv_block_manager.WindowGroup``).
  Decode attends through ``paged_attention(..., layer=a, window=...)``,
  whose band walk never reads a table slot behind the window; a span
  through ``masked_attention`` with the same window.

A **routed** feed-forward block (``ffn_types[i] == "moe"``) routes in
float32 over all experts (``router_score``: a softmax, or sigmoids with
a selection bias) and computes the experts the description says
this program holds (``ops/moe.py``, dropless; ``expert_act``: SwiGLU or
an ungated squared ReLU; with ``latent`` in a narrower width, between a
projection down and one up), plus the shared expert;
a bucket's padding rows route nowhere.  The router's counts of a pass
(``ops.moe.STATS``, summed over the routed layers) ride back with the
sampled token, in front of the caches.  Every routed block also leaves
what it was given and what it made of it, for the first few rows of the
pass, in the **probe** (:func:`probe_shape`): a small device array that
rides through the programs like a cache and is never read by one, so
that what a serving program really computed can be checked against a
reference afterwards without serving anything for the check
(``Engine.routed_probe``).

**What a description brings** is read off the description, never off its
class: state-space layers bring the state pools and a slot operand,
window layers the window group's caches, table and write targets, a
routed block the counts and the probe (:func:`extra_caches`).

Program operands (``Engine._program_specs`` mirrors them): params, the
caches (``ck, cv`` of the whole-context group, then the description's
own in :func:`extra_caches`' order: ``ssm, conv``; ``wk, wv``;
``probe``), donated through, the host-fed operands of the ``gpt``
program of the same kind, then the description's own (a state slot,
``(B,)`` for decode, a scalar for prefill and chunk; a window group's
block table and write targets, in ``_window_operands``' order) and the
tail ``serve/programs.py::_finish`` takes, the ``gpt`` programs'
epilogue (the sampling triple in sampling mode, the rng key).  A span
attends through ``ops.attention.masked_attention``, as theirs do.
"""

from __future__ import annotations

import collections

import jax
import jax.numpy as jnp
import numpy as np

from ..models.generate import _ln
from ..models.moe import FULL, WINDOW
from ..ops import moe as moe_ops
from ..ops import ssm as ssm_ops
from ..ops.attention import masked_attention, paged_attention, score_scale
from .kv_block_manager import STATE_POOL_NO_PREFIX, WINDOW_NO_PREFIX
from .programs import _finish, _rope

__all__ = ["HybridCfg", "hybrid_cfg", "check_params", "refuse", "layer",
           "matmul_flops", "build_decode", "build_prefill", "build_chunk",
           "routed", "extra_caches", "probe_layers", "probe_shape",
           "describe", "SCAN_SCOPE", "PROBE_DECODE", "PROBE_SPAN"]

# named scope of the chunked scan: device-trace operation names carry it
SCAN_SCOPE = "ssd_chunked_scan"

# what the compiled programs close over: the decoder's description plus
# the position of each layer in its kind's stack (-1: not of that kind)
HybridCfg = collections.namedtuple("HybridCfg", ["dec", "kv_index",
                                                 "ssm_index"])


def hybrid_cfg(dec):
    """Each layer's place among the layers of ITS kind: the index of its
    K/V in its group's stack, or of its state in the state pool."""
    at = [dec.layer_types[:i].count(kind)
          for i, kind in enumerate(dec.layer_types)]
    return HybridCfg(
        dec,
        tuple(-1 if k == "mamba" else a
              for k, a in zip(dec.layer_types, at)),
        tuple(a if k == "mamba" else -1
              for k, a in zip(dec.layer_types, at)))


def routed(dec):
    """Whether some layer's feed-forward block is routed: the programs
    then return the router's counts and carry the probe."""
    return "moe" in dec.ffn_types


def extra_caches(dec):
    """The caches a description's programs take behind the whole-context
    K/V pair, by name and in operand order."""
    return ((("ssm", "conv") if dec.mamba_layers else ())
            + (("wk", "wv") if dec.window_layers else ())
            + (("probe",) if routed(dec) else ()))


# the probe's first axis: which kind of pass wrote the rows
PROBE_DECODE, PROBE_SPAN = 0, 1


def probe_layers(dec):
    """The layers the probe watches: every one with a routed block."""
    return tuple(i for i, t in enumerate(dec.ffn_types) if t == "moe")


def probe_shape(dec, rows):
    """``(pass kind, row, routed layer, [input | output], d_model)``: the
    first ``rows`` rows of the newest decode pass and of the newest span,
    as each of :func:`probe_layers`' routed blocks saw and left them (its
    normed input, its output; both zero for a bucket's padding rows)."""
    return (2, int(rows), len(probe_layers(dec)), 2, dec.d_model)


def check_params(dec, params):
    """The parameter dict against the description: every name, every
    shape.  Returns the engine's ``spec`` (the keys a gpt() checkpoint's
    ``detect_gpt_variant`` gives)."""
    want = dec.param_shapes()
    missing = sorted(set(want) - set(params))
    if missing:
        raise ValueError(f"{_what(dec)}: parameters missing: "
                         f"{missing[:4]}{' ...' if len(missing) > 4 else ''}")
    for k, shape in want.items():
        if tuple(params[k].shape) != tuple(shape):
            raise ValueError(f"{_what(dec)}: {k} has shape "
                             f"{tuple(params[k].shape)}, the description "
                             f"says {tuple(shape)}")
    return {"n_layers": dec.num_layers, "d_model": dec.d_model,
            "head_dim": dec.head_dim, "kv_heads": dec.kv_heads,
            "vocab": dec.vocab_size, "pos_table": None, "swiglu": True,
            "tied": dec.tied, "rmsnorm": True}


def _what(dec):
    return "routed-expert decoder" if routed(dec) else "hybrid decoder"


def describe(dec):
    """What the description's layers are, for ``statusz()``: how many of
    each mixer and feed-forward kind, and what its state-space layers
    and routed blocks are made of."""
    out = {"layers": dec.num_layers,
           "mixers": dict(collections.Counter(dec.layer_types)),
           "ffns": dict(collections.Counter(dec.ffn_types))}
    if dec.mamba_layers:
        out["state"] = {"heads": dec.mamba_heads, "groups": dec.mamba_groups,
                        "head_dim": dec.mamba_head_dim,
                        "state": dec.mamba_state, "chunk": dec.mamba_chunk}
    if routed(dec):
        out["experts"] = {"router": dec.router_score, "picks": dec.top_k,
                          "of": dec.num_experts,
                          "held": [dec.expert_offset, dec.expert_count],
                          "act": dec.expert_act, "latent": dec.latent,
                          "routed_scale": dec.routed_scale}
    return out


def refuse(dec, prefix_cache, spec_k, adapters, kv_dtype, quantize, tp,
           host_kv_bytes):
    """What an engine over a described decoder cannot do yet, each
    refused by name at construction (docs/how_to/serve.md, "Hybrid
    decoders" and "Routed-expert decoders")."""
    why = {
        "prefix_cache": (prefix_cache, "the prefix cache is wired into the "
                         "gpt() programs only"),
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
    if dec.mamba_layers:
        why.update(prefix_cache=(prefix_cache, STATE_POOL_NO_PREFIX))
    if dec.window_layers:
        why.update(prefix_cache=(prefix_cache, WINDOW_NO_PREFIX))
    if dec.window_layers or routed(dec):
        why.update(spec_k=(spec_k, "the verify program has no window group "
                           "and no routed feed-forward block"
                           + (", and would have to roll the recurrent "
                              "state back over rejected tokens"
                              if dec.mamba_layers else "")))
    if routed(dec):
        why.update(tp=(tp > 1, "experts under a mesh need the exchange of "
                       "rows between shards, which is not written"))
    for arg, (on, reason) in why.items():
        if on:
            raise ValueError(f"a {_what(dec)} cannot be served with "
                             f"{arg}: {reason}")


def matmul_flops(dec, rows, head_rows):
    """Operations of the matrix products of one pass over ``rows``
    positions that samples ``head_rows`` of them (a routed layer's held
    experts at the share of the picks a uniform router gives them)."""
    per = 0.0
    for k, s in dec.param_shapes().items():
        if not k.endswith("_weight") or "tok_embed" in k or "conv" in k \
                or k.endswith("_head_weight"):
            continue
        n = 2 * int(np.prod(s))
        if "_experts_" in k:
            n = n * dec.top_k / dec.num_experts
        per += n
    return int(rows * per) + head_rows * 2 * dec.vocab_size * dec.d_model


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


def _mamba_bc(dec, xBC):
    """The convolved ``xBC``'s ``B`` and ``C`` columns as ``ops.ssm``
    takes them: ``(rows, G, N)``, or ``(rows, N)`` for one group."""
    di, G, N = dec.d_inner, dec.mamba_groups, dec.mamba_state
    Bm, Cm = xBC[:, di:di + G * N], xBC[:, di + G * N:]
    if G > 1:
        Bm, Cm = (v.reshape(v.shape[0], G, N) for v in (Bm, Cm))
    return Bm, Cm


def _mamba_out(dec, params, p, y, z):
    """Gate, then norm over each state group's channels (one group: all
    of d_inner), then the output projection."""
    g = (y.astype(jnp.float32) * _silu_f32(z)).astype(y.dtype)
    gamma, G = params[f"{p}_norm_gamma"], dec.mamba_groups
    if G > 1:
        rows = g.shape[0]
        g = _ln(g.reshape(rows, G, -1), gamma.reshape(G, -1), None,
                eps=dec.eps).reshape(rows, -1)
    else:
        g = _ln(g, gamma, None, eps=dec.eps)
    return _fc(g, params[f"{p}_out_proj_weight"])


def _swiglu(h, w_in, w_out):
    """``W_out (silu(g) * u)``, ``[g | u] = W_in h`` (gate rows first)."""
    gu = _fc(h, w_in)
    F = w_in.shape[0] // 2
    act = _silu_f32(gu[..., :F]).astype(gu.dtype) * gu[..., F:]
    return _fc(act, w_out)


def _relu2(h, w_in, w_out):
    """``W_out relu(W_in h)^2``: an ungated block."""
    return _fc(moe_ops.relu2(_fc(h, w_in)), w_out)


# a feed-forward block of each ``expert_act``
_FFN = {"swiglu": _swiglu, "relu2": _relu2}


def _qkv(dec, params, p, h, Hq=None):
    Hq = dec.num_heads if Hq is None else Hq
    Hkv, Dh = dec.kv_heads, dec.head_dim
    T = h.shape[0]
    qkv = _fc(h, params[f"{p}_qkv_weight"])
    return (qkv[:, :Hq * Dh].reshape(T, Hq, Dh),
            qkv[:, Hq * Dh:(Hq + Hkv) * Dh].reshape(T, Hkv, Dh),
            qkv[:, (Hq + Hkv) * Dh:].reshape(T, Hkv, Dh))


def _flat(u):
    """(T, heads, Dh) -> (T, heads * Dh): a position's heads side by side,
    the K/V cache's minor axis."""
    return u.reshape(u.shape[0], -1)


class _Mix:
    """What the three passes share: the description, the parameters and
    the caches under their names (``ck, cv`` and :func:`extra_caches`),
    the routed layers' counts summed as the stack goes, and the gated
    attention layer's front and back (heads, rotary, gate, output
    projection).  ``valid``: which rows are real, where a routed block
    has to know (None: all); ``probe_at``: the probe's pass kind."""

    valid = None

    def __init__(self, hc, params, caches):
        self.hc, self.params = hc, params
        self.names = ("ck", "cv") + extra_caches(hc.dec)
        assert len(caches) == len(self.names), (self.names, len(caches))
        for name, cache in zip(self.names, caches):
            setattr(self, name, cache)
        self.moe_stats = None

    @property
    def caches(self):
        return tuple(getattr(self, name) for name in self.names)

    def operands(self, rest):
        """Take the description's own operands off the front of ``rest``
        (a state slot, then the window group's); the tail is left."""
        dec, rest = self.hc.dec, list(rest)
        state = rest.pop(0) if dec.mamba_layers else None
        n = self.window_operands if dec.window_layers else 0
        window, self.tail = rest[:n], tuple(rest[n:])
        return state, window

    def watch(self, i, h, y):
        """Layer ``i``'s routed block took rows ``h`` and gave ``y``: the
        probe keeps the first of them (zeros for padding rows: a
        reference gives zero for zero)."""
        R = self.probe.shape[1]
        n = min(R, h.shape[0])
        rows = jnp.stack([h[:n], y[:n]], axis=1).astype(self.probe.dtype)
        if self.valid is not None:
            rows = jnp.where(self.valid[:n, None, None], rows,
                             jnp.zeros((), rows.dtype))
        # a pass narrower than the probe leaves zeros, not an older pass
        rows = jnp.pad(rows, ((0, R - n), (0, 0), (0, 0)))
        at = probe_layers(self.hc.dec).index(i)
        self.probe = self.probe.at[self.probe_at, :, at].set(rows)

    def _window(self, i):
        """Gated layer ``i``'s window (0: it sees the whole context)."""
        dec = self.hc.dec
        return dec.window if dec.layer_types[i] == WINDOW else 0

    def _write_kv(self, i, blk, k, v):
        """Layer ``i``'s K/V rows into its GROUP's stack at ``(its place
        there, blk, off)``; the group's caches as they now are."""
        at = (self.hc.kv_index[i], blk, self.off)
        if self._window(i):
            self.wk, self.wv = self.wk.at[at].set(k), self.wv.at[at].set(v)
            return self.wk, self.wv
        self.ck, self.cv = self.ck.at[at].set(k), self.cv.at[at].set(v)
        return self.ck, self.cv

    def _gated_qkv(self, i, h, pos):
        """Layer ``i``'s query, key and value heads, the first two turned
        by its kind's rotary scheme at the rows' positions."""
        dec = self.hc.dec
        q, k, v = _qkv(dec, self.params, f"{dec.name}_l{i}", h,
                       dec.heads[i])
        rope = dec.rope_of(dec.layer_types[i])
        kw = dict(inv=rope.inv_freq(), rot=rope.dim,
                  scale=None if rope.yarn is None else rope.scale)
        return _rope(q, pos, **kw), _rope(k, pos, **kw), v

    def _gated_out(self, i, h, at):
        """Each head's output times its gate (a sigmoid of the layer's
        normed input, float32), then the output projection."""
        dec = self.hc.dec
        p = f"{dec.name}_l{i}"
        T, H = h.shape[0], dec.heads[i]
        gate = jax.nn.sigmoid(
            _fc(h, self.params[f"{p}_gate_weight"]).astype(jnp.float32))
        at = (at.reshape(T, H, dec.head_dim).astype(jnp.float32)
              * gate[:, :, None]).astype(h.dtype)
        return _fc(at.reshape(T, -1), self.params[f"{p}_proj_weight"])


class _DecodeMix(_Mix):
    """One position for each of B rows: K/V through the block tables,
    states at ``(layer, slots[b])``.  ``rest``: the description's own
    operands (state slots ``(B,)``; the window group's tables) and the
    tail."""

    window_operands = 1
    probe_at = PROBE_DECODE

    def __init__(self, hc, params, caches, pos, tables, rest, block_size):
        super().__init__(hc, params, caches)
        self.pos, self.tables = pos, tables
        self.slots, window = self.operands(rest)

        def slot(tab):
            return jnp.take_along_axis(
                tab, (pos // block_size)[:, None], axis=1)[:, 0]

        self.blk = slot(tables)
        self.off = pos % block_size
        self.ctx = pos + 1
        if window:
            self.wtables, = window
            self.wblk = slot(self.wtables)
        if routed(hc.dec):
            # a bucket's padding rows write to the null block: they route
            # nowhere
            self.valid = self.blk != 0

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

    def gated_attention(self, i, h):
        q, k, v = self._gated_qkv(i, h, self.pos)
        window = self._window(i)
        tables, blk = ((self.wtables, self.wblk) if window
                       else (self.tables, self.blk))
        ck, cv = self._write_kv(i, blk, k, v)
        at = paged_attention(q, ck, cv, tables, self.ctx,
                             layer=self.hc.kv_index[i], window=window)
        return self._gated_out(i, h, at)

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
        di = dec.d_inner
        x = xBC[:, :di].reshape(B, dec.mamba_heads, dec.mamba_head_dim)
        dt, dA = _mamba_steps(dec, P, p, dt)
        y, self.ssm = ssm_ops.ssm_state_update(
            self.ssm, m, self.slots, x, dt, dA, *_mamba_bc(dec, xBC),
            P[f"{p}_D"])
        return _mamba_out(dec, P, p, y.reshape(B, di), z)


class _SpanMix(_Mix):
    """A span of ONE request: rows hold positions [start, start +
    n_valid), rows past n_valid are padding.  ``table`` None: a whole
    prompt from position 0 (dense causal attention inside the span, the
    state from zero); else a chunk that attends through the table and
    carries the slot's state.  ``rest``: the description's own operands
    (a state slot; the window group's write blocks, behind its table for
    a chunk) and the tail."""

    probe_at = PROBE_SPAN

    def __init__(self, hc, params, caches, T, start, n_valid, rest,
                 blk, off, table):
        super().__init__(hc, params, caches)
        self.T, self.start = T, start
        self.n_valid, self.blk, self.off, self.table = n_valid, blk, off, table
        self.valid = jnp.arange(T, dtype=jnp.int32) < n_valid
        self.window_operands = 1 if table is None else 2
        self.slot, window = self.operands(rest)
        if window:
            *wtable, self.wblk = window
            self.wtable = wtable[0] if wtable else None

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

    def gated_attention(self, i, h):
        dec, a = self.hc.dec, self.hc.kv_index[i]
        Hkv, Dh = dec.kv_heads, dec.head_dim
        q, k, v = self._gated_qkv(
            i, h, self.start + jnp.arange(self.T, dtype=jnp.int32))
        window = self._window(i)
        table, blk = ((self.wtable, self.wblk) if window
                      else (self.table, self.blk))
        # a window layer's rows that no later position can see go to the
        # null block (the host gives them block 0)
        ck, cv = self._write_kv(i, blk, k, v)
        if table is not None:
            k = ck[a, table].reshape(-1, Hkv, Dh)
            v = cv[a, table].reshape(-1, Hkv, Dh)
        at = masked_attention(q, k, v, self.start, score_scale(Dh),
                              window=window, n_valid=self.n_valid)
        return self._gated_out(i, h, at)

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
                xBC[:, :di].reshape(T, H, Pd), dt, dA,
                *_mamba_bc(dec, xBC), P[f"{p}_D"], S0, dec.mamba_chunk)
        self.ssm = self.ssm.at[m, self.slot].set(S)
        return _mamba_out(dec, P, p, y.reshape(T, di), z)


# which mixer a layer kind is (None: the layer has no mixer branch)
_MIXER = {"attention": "attention", "mamba": "mamba", "none": None,
          FULL: "gated_attention", WINDOW: "gated_attention"}


def _routed(dec, params, p, h, mix):
    """A routed feed-forward block over rows ``h``: the shared expert
    plus ``routed_scale`` times the held experts' part.  The router is
    float32 all the way (logits, scores, the ``top_k`` weights).  With a
    latent the experts work on ``W_down h`` and their weighted sum, this
    program's PART of it, goes through ``W_up``."""
    logits = jnp.dot(h.astype(jnp.float32),
                     params[f"{p}_router_weight"].T.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    idx, w = moe_ops.route(logits, dec.top_k, dec.router_score,
                           params.get(f"{p}_router_bias"))
    u = _fc(h, params[f"{p}_latent_down_weight"]) if dec.latent else h
    y, stats = moe_ops.routed_experts(
        u, params[f"{p}_experts_in_weight"],
        params[f"{p}_experts_out_weight"], idx, w, dec.expert_offset,
        dec.num_experts, valid=mix.valid, act=dec.expert_act)
    mix.moe_stats = stats if mix.moe_stats is None else mix.moe_stats + stats
    shared = _FFN[dec.expert_act](h, params[f"{p}_shared_in_weight"],
                                  params[f"{p}_shared_out_weight"])
    if dec.latent:
        y = _fc((np.float32(dec.routed_scale) * y).astype(h.dtype),
                params[f"{p}_latent_up_weight"])
        return (shared.astype(jnp.float32)
                + y.astype(jnp.float32)).astype(h.dtype)
    return (shared.astype(jnp.float32)
            + np.float32(dec.routed_scale) * y).astype(h.dtype)


def layer(hc, params, i, x, mix):
    """THE layer of a described decoder: the branches its description
    gives it, ``norm -> mixer(kind)`` then ``norm -> ffn(kind)``, each
    scaled by the residual multiplier and added to the stream; a kind of
    ``"none"`` is a branch the layer does not have.  ``mix`` carries the
    caches and the pass's operands and answers the mixer the layer's
    kind names (``_MIXER``)."""
    dec = hc.dec
    p = f"{dec.name}_l{i}"
    mixer, ffn = _MIXER[dec.layer_types[i]], dec.ffn_types[i]
    if mixer is not None:
        h = _ln(x, params[f"{p}_ln1_gamma"], None, eps=dec.eps)
        y = getattr(mix, mixer)(i, h)
        x = _residual(x, y, dec.residual_multiplier)
    if ffn == "none":
        return x
    h = _ln(x, params[f"{p}_ln2_gamma"], None, eps=dec.eps)
    if ffn == "moe":
        y = _routed(dec, params, p, h, mix)
        mix.watch(i, h, y)
    else:
        y = _swiglu(h, params[f"{p}_ff_in_weight"],
                    params[f"{p}_ff_out_weight"])
    return _residual(x, y, dec.residual_multiplier)


def _embed(dec, params, toks):
    e = params[f"{dec.name}_tok_embed_weight"][toks]
    return (e.astype(jnp.float32)
            * np.float32(dec.embedding_multiplier)).astype(e.dtype)


def _logits(dec, params, x):
    h = _ln(x, params[f"{dec.name}_ln_f_gamma"], None, eps=dec.eps)
    head = params[f"{dec.name}_tok_embed_weight" if dec.tied
                  else f"{dec.name}_head_weight"]
    lg = h @ head.T.astype(h.dtype)
    return (lg.astype(jnp.float32)
            / np.float32(dec.logits_scaling)).astype(lg.dtype)


def _stack(hc, params, x, mix):
    for i in range(hc.dec.num_layers):
        x = layer(hc, params, i, x, mix)
    return x


def _outs(cfg, mix, logits, scalar):
    """``_finish``'s outputs, with a routed block's router counts between
    the sampled token's outputs and the watchdog flag / caches."""
    outs = _finish(cfg, logits, mix.caches, mix.tail, scalar=scalar)
    if not routed(mix.hc.dec):
        return outs
    n = 4 if cfg.sampling else 1
    return outs[:n] + (mix.moe_stats,) + outs[n:]


def _jit(fn, hc, donate):
    """``fn(params, *caches, *operands)``, the caches donated through."""
    n = 2 + len(extra_caches(hc.dec))

    def program(params, *args):
        return fn(params, args[:n], *args[n:])

    program.__name__ = fn.__name__
    return jax.jit(program,
                   donate_argnums=tuple(range(1, 1 + n)) if donate else ())


def build_decode(cfg, donate):
    hc = cfg.hybrid

    def decode(params, caches, toks, pos, tables, *rest):
        mix = _DecodeMix(hc, params, caches, pos, tables, rest,
                         cfg.block_size)
        x = _stack(hc, params, _embed(hc.dec, params, toks), mix)
        return _outs(cfg, mix, _logits(hc.dec, params, x), scalar=False)

    return _jit(decode, hc, donate)


def build_prefill(cfg, P, donate):
    hc = cfg.hybrid

    def prefill(params, caches, toks, plen, blk, off, *rest):
        """Whole prompt at padded length P for ONE request, from
        position 0: the slot's state is written, never read."""
        mix = _SpanMix(hc, params, caches, P, 0, plen, rest, blk, off, None)
        x = _stack(hc, params, _embed(hc.dec, params, toks), mix)
        return _outs(cfg, mix, _logits(hc.dec, params, x[plen - 1][None]),
                     scalar=True)

    return _jit(prefill, hc, donate)


def build_chunk(cfg, C, donate):
    hc = cfg.hybrid

    def chunk(params, caches, toks, start, n_valid, table, blk, off, *rest):
        """C rows of ONE request at positions [start, start + n_valid):
        attends through the table, starts from the slot's state (from
        zero at start 0) and writes it back."""
        mix = _SpanMix(hc, params, caches, C, start, n_valid, rest, blk,
                       off, table)
        x = _stack(hc, params, _embed(hc.dec, params, toks), mix)
        return _outs(cfg, mix,
                     _logits(hc.dec, params, x[n_valid - 1][None]),
                     scalar=True)

    return _jit(chunk, hc, donate)
