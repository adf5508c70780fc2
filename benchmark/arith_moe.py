"""Operations and bytes of a routed-expert decoder with window and global
attention layers, kept with the benchmark so that a later change to the
program cannot move the yardstick.  ``d`` is ``moe_cell.dims(cfg)``.  One
multiply-accumulate counts as 2 operations; elementwise, normalisation,
rotary, routing and sorting work counts as free.  Everything is counted
for THIS program's share: the experts it holds, the vocabulary it holds.
"""


def attn_params(d, heads):
    """Matrix parameters of one attention layer of ``heads`` query heads:
    q, k, v, the per-head gate and the output projection."""
    D, Dh = d["d_model"], d["head_dim"]
    return ((heads + 2 * d["kv_heads"]) * Dh * D + heads * D
            + D * heads * Dh)


def expert_params(d):
    """One routed expert: up-gate and down."""
    return 3 * d["d_model"] * d["expert_ff"]


def layer_fixed_params(d, i):
    """Matrix parameters every token of layer ``i`` is multiplied with:
    attention, and the dense block or the router and the shared expert."""
    D = d["d_model"]
    fixed = attn_params(d, d["heads"][i])
    if d["dense"][i]:
        return fixed + 3 * D * d["d_ff"]
    return fixed + d["num_experts"] * D + 3 * D * d["shared_ff"]


def param_count(d):
    """Every parameter this program holds."""
    D = d["d_model"]
    n = 2 * d["vocab"] * D + D
    for i in range(d["layers"]):
        n += layer_fixed_params(d, i) + 2 * D
        if not d["dense"][i]:
            n += d["held"] * expert_params(d)
    return n


def n_moe(d):
    return sum(not dense for dense in d["dense"])


def held_picks_expected(d, rows):
    """Held picks a routed layer gets from ``rows`` tokens under a router
    that favours no expert."""
    return rows * d["top_k"] * d["held"] / d["num_experts"]


def attn_context(d, i, context):
    """Keys a query at the end of ``context`` positions sees in layer i."""
    return min(context, d["window"]) if d["window_layer"][i] else context


def token_flops(d, context, held_picks=None, head=True):
    """Operations for ONE token that attends over ``context`` positions,
    with ``held_picks`` picks of held experts summed over the routed layers
    (default: a uniform router's share)."""
    if held_picks is None:
        held_picks = n_moe(d) * held_picks_expected(d, 1)
    ops = 2 * held_picks * expert_params(d)
    for i in range(d["layers"]):
        ops += 2 * layer_fixed_params(d, i)
        ops += 4 * d["heads"][i] * d["head_dim"] * attn_context(d, i, context)
    if head:
        ops += 2 * d["vocab"] * d["d_model"]
    return ops


def prefill_flops(d, span, end):
    """A prefill pass of ``span`` real tokens whose last attends over
    ``end`` positions (each token at the context it really has; one head
    position)."""
    ops = span * token_flops(d, 0, head=False) \
        + 2 * d["vocab"] * d["d_model"]
    first = end - span
    for i in range(d["layers"]):
        if d["window_layer"][i]:
            # contexts first + 1 .. end, each cut at the window
            W = d["window"]
            rising = max(0, min(W, end) - first)
            keys = sum(range(first + 1, first + rising + 1)) \
                + (span - rising) * W
        else:
            keys = sum(range(first + 1, end + 1))
        ops += 4 * d["heads"][i] * d["head_dim"] * keys
    return ops


def decode_attn_keys(d, i, rows, contexts):
    """Keys layer ``i`` shows ``rows`` decoding rows whose contexts sum
    to ``contexts``.  A step's record keeps the sum only: a window layer
    is counted at ``min(window, mean context)`` a row, exact where every
    row's context is past the window (or none is), above otherwise."""
    if not d["window_layer"][i] or not rows:
        return contexts
    return rows * min(d["window"], contexts / rows)


def window_flops(d, steps):
    """Operations the model needs for every real token the steps decoded
    or prefilled (held picks at a uniform router's share).  A step is
    ``readers.py``'s tuple: (stamp, requests decoded, sum of their
    contexts, prefill passes [(span, end)], ...)."""
    total = 0
    for s in steps:
        total += s[1] * token_flops(d, 0)
        total += sum(4 * d["heads"][i] * d["head_dim"]
                     * decode_attn_keys(d, i, s[1], s[2])
                     for i in range(d["layers"]))
        total += sum(prefill_flops(d, span, end) for span, end in s[3])
    return total


def paged_kv_bytes(d, rows, contexts, itemsize=2):
    """Bytes the decode attention has to read for ``rows`` rows whose
    contexts sum to ``contexts``: K and V of the global layers over the
    whole context, of the window layers over at most ``window``
    positions a row."""
    per = 2 * d["kv_heads"] * d["head_dim"] * itemsize
    return sum(per * decode_attn_keys(d, i, rows, contexts)
               for i in range(d["layers"]))


def moe_bytes(d, experts_hit, picks_held, itemsize=2):
    """Bytes the held experts' work has to move: the matrices of the
    experts hit (summed over layers), and each held pick's row in and
    out."""
    return (experts_hit * expert_params(d) * itemsize
            + picks_held * 2 * d["d_model"] * itemsize)


def moe_flops(d, picks_held):
    return 2 * picks_held * expert_params(d)


def decode_step_bytes(d, rows, contexts, experts_hit, itemsize=2):
    """Bytes one decode step has to move: every weight that is not a
    routed expert's once (embedding rows aside: one row a token), the
    experts hit, and the K and V the rows' contexts show."""
    fixed = param_count(d) - n_moe(d) * d["held"] * expert_params(d) \
        - d["vocab"] * d["d_model"]
    return (fixed * itemsize + experts_hit * expert_params(d) * itemsize
            + paged_kv_bytes(d, rows, contexts, itemsize))
