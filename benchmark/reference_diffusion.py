"""The plain reference of a block-diffusion decoder with routed experts
(SDAR: Qwen3-MoE's layer under a block-causal mask), the benchmark's own
copy: it shares no code with ``mxnet_tpu`` and reads the program's
parameter dict by the names ``mx.models.moe_decoder`` gives its
parameters.  ``cfg`` is the configuration file's dictionary (the
source's keys).

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no cache, no kernel, no
bucket padding of the model's own, no sorting of picks; every expert runs
on every row and a row keeps its own picks' share.

    h = x + Attn(RMSNorm(x));  y = h + MoE(RMSNorm(h));  eps 1e-6
    logits = RMSNorm(y_L) W_head^T           (untied head, NO shift)

    Attn: 32 query heads over 4 key-value heads of 128, no bias;
      q_h = RoPE(RMSNorm_q(u W_q,h)), k_g = RoPE(RMSNorm_k(u W_k,g)), the
      norms per head over its 128 dims (one gain of 128 a layer); rotary
      rotate-half over all 128 dims at base 1e6; head h reads kv head
      h // 8; scores / sqrt(128); a query at position p sees the keys at
      j < (p // B + 1) * B (block-causal); no output gate.
    MoE: router logits u W_r over all 128 experts in float32, softmax,
      the 8 largest divided by their sum; experts SwiGLU of 768; no
      shared expert.

Generation is checked, not replayed free: ``replay`` teacher-forces a
request's blocks by the engine's own unmask record.  The committed
sequence (prompt and clean blocks) runs once under the block-causal mask;
every denoise step of every block is a separate ROW GROUP of B positions
(the block as it stood before that step) that attends to the clean
context before its block and to its own B positions, so all steps of a
request run in one batched pass.  The head runs on those rows only, in
chunks.

``fault`` (``diffusion_controls.py``) computes a deliberately different
model or schedule; ``None`` is the configuration's.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
NAME = "moe"
FAULTS = ("causal_in_block", "no_qk_norm", "shift", "left_to_right",
          "stale_kv", "acc_bf16", "router_bf16")
# rows of the clean sequence and of the step groups are padded to these
# multiples, so that requests of many lengths share few compiled passes
CLEAN_PAD = 512
GROUP_PAD = 128
# query rows a head chunk and an attention chunk take at a time
CHUNK = 512


def _f32(x):
    return jnp.asarray(x).astype(F32)


class _Dims(dict):
    """The sizes, hashable: the jitted passes take them as static."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def dims(cfg):
    """The sizes the equations need, from the source's keys."""
    assert not cfg["attention_bias"] and not cfg["tie_word_embeddings"]
    assert cfg["norm_topk_prob"] and cfg["mlp_only_layers"] == []
    assert cfg["decoder_sparse_step"] == 1 and cfg["rope_scaling"] is None
    assert not cfg["use_sliding_window"] and cfg["qk_norm"]
    assert cfg["remasking"] == "low_confidence_static"
    return _Dims({"D": cfg["hidden_size"], "V": cfg["vocab_size"],
            "L": cfg["num_hidden_layers"], "H": cfg["num_attention_heads"],
            "Hkv": cfg["num_key_value_heads"], "Dh": cfg["head_dim"],
            "E": cfg["num_experts"], "k": cfg["num_experts_per_tok"],
            "F": cfg["moe_intermediate_size"], "eps": cfg["rms_norm_eps"],
            "theta": float(cfg["rope_theta"]), "B": cfg["block_length"],
            "T": cfg["denoising_steps"], "mask": cfg["mask_token_id"]})


def transfer_counts(masks, steps):
    """SDAR's ``get_num_transfer_tokens`` for one block: ``masks //
    steps`` a step, one more in the first ``masks % steps``."""
    base, extra = divmod(int(masks), int(steps))
    return [base + (s < extra) for s in range(int(steps))]


def _rms(x, gamma, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * _f32(gamma)


def _rope(x, pos, theta):
    """Rotate-half rotary over all of a head's dims at ``pos``."""
    Dh = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, Dh, 2, dtype=np.float64) / Dh)
    ang = pos.astype(F32)[:, None] * jnp.asarray(inv, F32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :Dh // 2], x[..., Dh // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def _to_bf16(x):
    """Round float32 values to what bfloat16 holds, and stay float32
    (``lax.reduce_precision``: an ``astype`` round trip inside one fusion
    may be kept in float32 on the chip)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _matmul(x, w, fault):
    """x (T, K) @ w (K, N); under ``acc_bf16`` the sum over K is carried
    in bfloat16, 128 terms at a time in float32."""
    if fault != "acc_bf16":
        return x @ w
    K = x.shape[1]
    step = 128 if K % 128 == 0 else K
    xs = x.reshape(x.shape[0], K // step, step).transpose(1, 0, 2)
    terms = jnp.einsum("jtk,jkn->jtn", xs, w.reshape(K // step, step, -1))

    def add(acc, term):
        return _to_bf16(acc + term), None
    out, _ = jax.lax.scan(add, jnp.zeros(terms.shape[1:], F32), terms)
    return out


@functools.partial(jax.jit, static_argnames=("d", "fault"))
def _qkv(u, w, pos, d, fault):
    """Queries (T, H, Dh), keys and values (T, Hkv, Dh) of normed rows
    ``u`` at positions ``pos``."""
    H, Hkv, Dh = d["H"], d["Hkv"], d["Dh"]
    T = u.shape[0]
    qkv = u @ _f32(w["qkv_weight"]).T
    q = qkv[:, :H * Dh].reshape(T, H, Dh)
    k = qkv[:, H * Dh:(H + Hkv) * Dh].reshape(T, Hkv, Dh)
    v = qkv[:, (H + Hkv) * Dh:].reshape(T, Hkv, Dh)
    if fault != "no_qk_norm":
        q = _rms(q, w["q_norm_gamma"], d["eps"])
        k = _rms(k, w["k_norm_gamma"], d["eps"])
    return _rope(q, pos, d["theta"]), _rope(k, pos, d["theta"]), v


def _scores(q, k, d):
    """(R, Hkv, G, S) scores of queries (R, H, Dh) over keys (S, Hkv,
    Dh)."""
    R, Hkv = q.shape[0], d["Hkv"]
    qg = q.reshape(R, Hkv, d["H"] // Hkv, d["Dh"])
    return jnp.einsum("rkgd,skd->rkgs", qg, k) / F32(math.sqrt(d["Dh"]))


def _fold(s, v, keep):
    """One part of a softmax over masked scores ``s (R, Hkv, G, S)``:
    (max, sum, weighted values) for a later merge."""
    s = jnp.where(keep[:, None, None, :], s, -jnp.inf)
    m = s.max(-1)
    safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(s - safe[..., None])
    return safe, p.sum(-1), jnp.einsum("rkgs,skd->rkgd", p, v)


@functools.partial(jax.jit, static_argnames=("d", "fault"))
def _clean_attention(q, k, v, d, fault):
    """The committed sequence under the mask (rows = positions)."""
    N, B = q.shape[0], d["B"]
    j = jnp.arange(N)

    def rows(args):
        i0, qc = args
        i = i0 + jnp.arange(qc.shape[0])
        keep = (j[None] <= i[:, None] if fault == "causal_in_block"
                else j[None] < (i[:, None] // B + 1) * B)
        _, l, acc = _fold(_scores(qc, k, d), v, keep)
        return acc / l[..., None]

    C = min(CHUNK, N)
    out = jax.lax.map(rows, (jnp.arange(0, N, C),
                             q.reshape(N // C, C, d["H"], d["Dh"])))
    return out.reshape(N, d["H"] * d["Dh"])


@functools.partial(jax.jit, static_argnames=("d", "fault"))
def _group_attention(q, k, v, starts, ck, cv, d, fault):
    """Step groups (rows ``g * B + b``) over the context keys before
    their block (``ck``/``cv``, positions 0..) and their own B rows."""
    R, B, H, Dh, Hkv = q.shape[0], d["B"], d["H"], d["Dh"], d["Hkv"]
    Q = R // B
    j = jnp.arange(ck.shape[0])

    def rows(args):
        qc, st = args
        keep = j[None] < jnp.repeat(st, B)[:, None]
        return _fold(_scores(qc, ck, d), cv, keep)

    C = min(CHUNK, R)
    m1, l1, a1 = jax.lax.map(rows, (q.reshape(R // C, C, H, Dh),
                                    starts.reshape(R // C, C // B)))
    m1, l1 = m1.reshape(R, Hkv, H // Hkv), l1.reshape(R, Hkv, H // Hkv)
    a1 = a1.reshape(R, Hkv, H // Hkv, Dh)
    # the own block: (Q, B queries, Hkv, G) over (Q, B keys)
    qg = q.reshape(Q, B, Hkv, H // Hkv, Dh)
    s = jnp.einsum("qbkgd,qckd->qbkgc", qg, k.reshape(Q, B, Hkv, Dh)) \
        / F32(math.sqrt(Dh))
    if fault == "causal_in_block":
        own = jnp.arange(B)[:, None] >= jnp.arange(B)[None, :]
        s = jnp.where(own[None, :, None, None, :], s, -jnp.inf)
    m2 = s.max(-1)
    p = jnp.exp(s - m2[..., None])
    l2 = p.sum(-1).reshape(R, Hkv, H // Hkv)
    a2 = jnp.einsum("qbkgc,qckd->qbkgd", p,
                    v.reshape(Q, B, Hkv, Dh)).reshape(R, Hkv, H // Hkv, Dh)
    m2 = m2.reshape(R, Hkv, H // Hkv)
    m = jnp.maximum(m1, m2)
    e1, e2 = jnp.exp(m1 - m), jnp.exp(m2 - m)
    out = (a1 * e1[..., None] + a2 * e2[..., None]) \
        / (l1 * e1 + l2 * e2)[..., None]
    return out.reshape(R, H * Dh)


@functools.partial(jax.jit, static_argnames=("k", "fault"))
def _shares(u, w_r, k, fault):
    """(T, E): the weight each expert has on each row."""
    logits = u @ _f32(w_r).T
    if fault == "router_bf16":
        logits = _to_bf16(logits)
    pr = jax.nn.softmax(logits, -1)
    if fault == "router_bf16":
        pr = _to_bf16(pr)
    w, idx = jax.lax.top_k(pr, k)
    w = w / w.sum(-1, keepdims=True)
    mine = idx[:, :, None] == jnp.arange(w_r.shape[0])[None, None, :]
    return jnp.sum(jnp.where(mine, w[:, :, None], 0.0), 1)


@functools.partial(jax.jit, static_argnames=("fault",))
def _expert(u, share, w_in, w_out, fault):
    gu = _matmul(u, _f32(w_in), fault)
    F = w_in.shape[1] // 2
    act = gu[:, :F] * jax.nn.sigmoid(gu[:, :F]) * gu[:, F:]
    return share[:, None] * _matmul(act, _f32(w_out), fault)


@functools.partial(jax.jit, static_argnames=("k",))
def _picks(u, w_r, k):
    """(T, k) the experts each row picks, and (T,) how far its last pick's
    router logit lies above the best one not picked."""
    top, idx = jax.lax.top_k(u @ _f32(w_r).T, k + 1)
    return idx[:, :k], top[:, k - 1] - top[:, k]


def router(cfg, params, i, u, name=NAME):
    """Layer ``i``'s picks over normed rows ``u (T, D)``: ``(experts (T,
    k), gap (T,))``."""
    d = dims(cfg)
    with jax.default_matmul_precision("highest"):
        return _picks(_f32(u), params[f"{name}_l{i}_router_weight"],
                      k=d["k"])


def _only(fault, *names):
    """``fault`` where a jitted pass computes it, else None: a fault
    another pass computes compiles nothing anew."""
    return fault if fault in names else None


def _weights(params, i, name=NAME):
    pre = f"{name}_l{i}_"
    return {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}


def ffn(cfg, params, i, u, fault=None, name=NAME):
    """Layer ``i``'s routed block over normed rows ``u (T, D)`` float32."""
    d = dims(cfg)
    w = _weights(params, i, name)
    with jax.default_matmul_precision("highest"):
        share = _shares(_f32(u), w["router_weight"], k=d["k"],
                        fault=_only(fault, "router_bf16"))
        out = jnp.zeros(u.shape, F32)
        for e in range(d["E"]):
            out = out + _expert(_f32(u), share[:, e],
                                w["experts_in_weight"][e],
                                w["experts_out_weight"][e],
                                _only(fault, "acc_bf16"))
        return out


@jax.jit
def _norm(x, gamma, eps):
    return _rms(x, gamma, eps)


@functools.partial(jax.jit, static_argnames=("mask",))
def _head_rows(h, w_head, toks, mask):
    """For each row: its greedy token and confidence with ``mask``
    excluded, the best logit, and the logit of ``toks``."""
    lg = h @ _f32(w_head).T
    lg = jnp.where(jnp.arange(lg.shape[-1]) == mask, -jnp.inf, lg)
    top = lg.max(-1)
    conf = 1.0 / jnp.exp(lg - top[:, None]).sum(-1)
    chosen = jnp.take_along_axis(lg, toks[:, None], 1)[:, 0]
    finite = jnp.where(jnp.isfinite(lg), lg, 0.0)
    return conf, top, chosen, jnp.sqrt(jnp.mean(jnp.square(
        finite - finite.mean(-1, keepdims=True)), -1))


def _pad_to(n, m):
    return max(m, -(-n // m) * m)


def blocks_of(prompt, log, B, mask):
    """The blocks a request's unmask record describes: ``[(start, fixed
    tokens, {position: (token, step)})]`` in order, the first opened by
    the prompt's last ``P mod B`` tokens."""
    P = len(prompt)
    n0 = P - P % B
    by_block = {}
    for pos, tok, step in log:
        by_block.setdefault((pos - n0) // B, {})[pos] = (int(tok), int(step))
    out = []
    for b in range(max(by_block) + 1 if by_block else 0):
        start = n0 + b * B
        fixed = [int(t) for t in prompt[start:start + B]] if b == 0 else []
        out.append((start, fixed, by_block.get(b, {})))
    return out


def prompt_inputs(cfg, params, prompt, fault=None, name=NAME):
    """Layer 0's feed-forward input ``(n, D)`` over a prompt's whole
    blocks ``n``, as a prefill computes them: the first routed block's
    input, behind the block-causal attention, the QK norms and the rotary
    and in front of any router.  Its first positions see a few keys, so a
    mask that is causal inside the block moves them by whole units."""
    d = dims(cfg)
    B = d["B"]
    n = len(prompt) - len(prompt) % B
    Np = _pad_to(n, CLEAN_PAD)
    toks = np.zeros(Np, np.int32)
    toks[:n] = np.asarray(prompt[:n])
    w = _weights(params, 0, name)
    eps = F32(d["eps"])
    with jax.default_matmul_precision("highest"):
        x = _f32(params[f"{name}_tok_embed_weight"][jnp.asarray(toks)])
        q, k, v = _qkv(_norm(x, w["ln1_gamma"], eps), w,
                       jnp.arange(Np), d=d, fault=_only(fault, "no_qk_norm"))
        x = x + _clean_attention(q, k, v, d=d,
                                 fault=_only(fault, "causal_in_block")) \
            @ _f32(w["proj_weight"]).T
        return np.asarray(_norm(x, w["ln2_gamma"], eps))[:n]


def replay(cfg, params, prompt, committed, current=(), probe=None,
           fault=None, name=NAME):
    """Teacher-force one request's blocks by its unmask record.

    ``committed``: the ``(position, token, step)`` of its committed
    blocks; ``current``: those of the block it is denoising.  ``probe``:
    ``(start, block tokens)`` of one more row group (the block as the
    window's last decode pass took it), whose layer-0 feed-forward input
    is returned.  Returns, over every denoise step: ``regrets`` (at each
    position the engine unmasked, the reference's best logit without the
    mask id minus its logit of the engine's token), ``order_regrets``
    (the reference's n-th highest confidence among the masked positions,
    n the step's count, minus its lowest at the engine's choices; under
    ``left_to_right`` the reference's order is position order, and a
    choice other than the leftmost reads 1), ``schedule_ok`` (every
    committed block unmasked its positions in the static schedule's
    counts), ``sequence`` (the committed tokens past the prompt, in
    order), ``kv0`` (layer 0's K and V, (2, B, Hkv, Dh), of the last
    committed block as the reference's clean block has them: under
    ``stale_kv`` as its last denoise step had them) and ``u0``."""
    d = dims(cfg)
    assert fault is None or fault in FAULTS, fault
    B, mask, T = d["B"], d["mask"], d["T"]
    prompt = [int(t) for t in prompt]
    P = len(prompt)
    n0 = P - P % B
    done = blocks_of(prompt, list(committed), B, mask)
    live = blocks_of(prompt, list(committed) + list(current), B, mask)
    live = live[len(done):]
    schedule_ok, sequence = True, []
    clean = prompt[:n0]
    for start, fixed, got in done:
        toks = list(fixed) + [None] * (B - len(fixed))
        steps = [0] * T
        for pos, (tok, step) in got.items():
            toks[pos - start] = tok
            steps[step] += 1
        want = transfer_counts(B - len(fixed), T)
        schedule_ok &= steps == want and None not in toks
        toks = [mask if t is None else t for t in toks]
        clean += toks
        sequence += toks[len(fixed):]
    # the row groups: every denoise step of every block, then the probe
    groups = []          # (start, block as it went in, chosen positions)
    last_step_of = {}    # block start -> index of its last group
    for start, fixed, got in done + live:
        state = list(fixed) + [mask] * (B - len(fixed))
        steps = sorted({s for _, s in got.values()})
        for s in steps:
            chosen = sorted(p for p, (_, st) in got.items() if st == s)
            groups.append((start, list(state), chosen))
            for p in chosen:
                state[p - start] = got[p][0]
        if steps:
            last_step_of[start] = len(groups) - 1
    n_steps = len(groups)
    if probe is not None:
        groups.append((int(probe[0]), [int(t) for t in probe[1]], []))
    N = len(clean)
    Np = _pad_to(N, CLEAN_PAD)
    Q = len(groups)
    Qp = _pad_to(Q, GROUP_PAD)
    ctoks = np.zeros(Np, np.int32)
    ctoks[:N] = clean
    gtoks = np.zeros((Qp, B), np.int32)
    starts = np.zeros(Qp, np.int32)
    for g, (start, state, _) in enumerate(groups):
        gtoks[g], starts[g] = state, start
    gpos = (starts[:, None] + np.arange(B)).reshape(-1)
    cpos = np.arange(Np)
    emb = params[f"{name}_tok_embed_weight"]
    eps = F32(d["eps"])
    kv0 = u0 = None
    with jax.default_matmul_precision("highest"):
        xc = _f32(emb[jnp.asarray(ctoks)])
        xg = _f32(emb[jnp.asarray(gtoks.reshape(-1))])
        for i in range(d["L"]):
            w = _weights(params, i, name)
            uc = _norm(xc, w["ln1_gamma"], eps)
            ug = _norm(xg, w["ln1_gamma"], eps)
            qkv_fault = _only(fault, "no_qk_norm")
            qc, kc, vc = _qkv(uc, w, jnp.asarray(cpos), d=d, fault=qkv_fault)
            qg, kg, vg = _qkv(ug, w, jnp.asarray(gpos), d=d, fault=qkv_fault)
            ck, cv = kc, vc
            if fault == "stale_kv" and last_step_of:
                # committed blocks keep what their last denoise step wrote
                src = np.full(Np, -1)
                for start, g in last_step_of.items():
                    if start + B <= N:
                        src[start:start + B] = g * B + np.arange(B)
                take = jnp.asarray(np.maximum(src, 0))
                stale = jnp.asarray(src >= 0)[:, None, None]
                ck = jnp.where(stale, kg[take], kc)
                cv = jnp.where(stale, vg[take], vc)
            if i == 0 and len(done):
                last = done[-1][0]
                kv0 = np.stack([np.asarray(ck[last:last + B]),
                                np.asarray(cv[last:last + B])])
            proj = _f32(w["proj_weight"]).T
            mask_fault = _only(fault, "causal_in_block")
            xc = xc + _clean_attention(qc, ck, cv, d=d,
                                       fault=mask_fault) @ proj
            xg = xg + _group_attention(qg, kg, vg, jnp.asarray(starts), ck,
                                       cv, d=d, fault=mask_fault) @ proj
            uc = _norm(xc, w["ln2_gamma"], eps)
            ug = _norm(xg, w["ln2_gamma"], eps)
            if i == 0 and probe is not None:
                u0 = np.asarray(ug[(Q - 1) * B:Q * B])
            both = ffn(cfg, params, i, jnp.concatenate([uc, ug]), fault,
                       name)
            xc, xg = xc + both[:Np], xg + both[Np:]
        hg = _norm(xg, params[f"{name}_ln_f_gamma"], eps)
        if fault == "shift":
            # the logits of the position BEFORE give a position's token
            hc = _norm(xc, params[f"{name}_ln_f_gamma"], eps)
            rows = hg.reshape(Qp, B, -1)
            prev = hc[jnp.maximum(jnp.asarray(starts) - 1, 0)]
            hg = jnp.concatenate([prev[:, None], rows[:, :-1]],
                                 1).reshape(Qp * B, -1)
        token_at = {q: t for blk in done + live
                    for q, (t, _) in blk[2].items()}
        want = np.zeros(Qp * B, np.int32)
        for g, (start, _, chosen) in enumerate(groups[:n_steps]):
            for p in chosen:
                want[g * B + p - start] = token_at[p]
        conf, top, chosen_lg, spread = [], [], [], []
        w_head = params[f"{name}_head_weight"]
        for c in range(0, Qp * B, CHUNK):
            out = _head_rows(hg[c:c + CHUNK], w_head,
                             jnp.asarray(want[c:c + CHUNK]), mask=mask)
            conf.append(np.asarray(out[0], np.float64))
            top.append(np.asarray(out[1], np.float64))
            chosen_lg.append(np.asarray(out[2], np.float64))
            spread.append(np.asarray(out[3], np.float64))
    conf, top = np.concatenate(conf), np.concatenate(top)
    chosen_lg, spread = np.concatenate(chosen_lg), np.concatenate(spread)
    regrets, order = [], []
    for g, (start, state, chosen) in enumerate(groups[:n_steps]):
        rows = g * B + np.arange(B)
        masked = np.asarray(state) == mask
        picked = [p - start for p in chosen]
        regrets += [float(top[g * B + b] - chosen_lg[g * B + b])
                    for b in picked]
        if fault == "left_to_right":
            first = int(np.flatnonzero(masked)[:len(picked)].max())
            order.append(float(max(picked) > first))
        else:
            c = conf[rows]
            best = np.sort(c[masked])[::-1][len(picked) - 1]
            order.append(float(best - min(c[b] for b in picked)))
    return {"regrets": regrets, "order_regrets": order,
            "schedule_ok": bool(schedule_ok), "sequence": sequence,
            "kv0": kv0, "u0": u0, "steps": n_steps,
            "logit_std": float(spread[:n_steps * B].mean())
            if n_steps else 0.0}

