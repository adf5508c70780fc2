"""Operations and bytes of a one-branch decoder (every layer a Mamba-2
mixer in state groups, an attention, or routed experts in a latent
width), kept with the benchmark so that a later change to the program
cannot move the yardstick.  ``d`` is ``branch_cell.dims(cfg)``.  One
multiply-accumulate counts as 2 operations; elementwise, normalisation,
convolution, routing and sorting work counts as free.  Everything is
counted for THIS program's share: the experts it holds, the vocabulary it
holds.
"""


def count(d, kind):
    return d["kinds"].count(kind)


def mamba_params(d):
    """Matrix parameters of one Mamba-2 layer: the input projection (z,
    x, B and C of every group, dt) and the output projection."""
    di = d["m_heads"] * d["m_head_dim"]
    return ((2 * di + 2 * d["groups"] * d["state"] + d["m_heads"])
            * d["d_model"] + d["d_model"] * di)


def mamba_small(d):
    """Its vectors: convolution taps and bias, dt_bias, A_log, D, the
    gated norm's gain."""
    di = d["m_heads"] * d["m_head_dim"]
    cd = di + 2 * d["groups"] * d["state"]
    return cd * d["conv"] + cd + 3 * d["m_heads"] + di


def attn_params(d):
    return ((d["num_heads"] + 2 * d["kv_heads"]) * d["head_dim"]
            * d["d_model"] + d["d_model"] * d["num_heads"] * d["head_dim"])


def expert_params(d):
    """One routed expert: two matrices in the latent width."""
    return 2 * d["latent"] * d["expert_ff"]


def moe_fixed_params(d):
    """What every token of a routed layer is multiplied with: the router,
    the latent pair and the shared expert."""
    D = d["d_model"]
    return (d["num_experts"] * D + 2 * d["latent"] * D
            + 2 * D * d["shared_ff"])


def fixed_params(d):
    """Matrix parameters a token is multiplied with in every layer, the
    routed experts and the head left out."""
    return (count(d, "mamba") * mamba_params(d)
            + count(d, "attention") * attn_params(d)
            + count(d, "moe") * moe_fixed_params(d))


def param_count(d):
    """Every parameter this program holds."""
    D = d["d_model"]
    return (fixed_params(d) + count(d, "mamba") * mamba_small(d)
            + count(d, "moe") * (d["held"] * expert_params(d)
                                 + d["num_experts"])
            + len(d["kinds"]) * D + D + 2 * d["vocab"] * D)


def held_picks_expected(d, rows):
    """Held picks a routed layer gets from ``rows`` tokens under a router
    that favours no expert."""
    return rows * d["top_k"] * d["held"] / d["num_experts"]


def token_flops(d, context, head=True):
    """Operations for ONE token that attends over ``context`` positions:
    the matrix products (held picks at a uniform router's share), the
    attention layers' q.k and p.v, the state layers' update and read-out
    (2 operations each per state element), the head if it is sampled."""
    ops = 2 * fixed_params(d)
    ops += 2 * count(d, "moe") * held_picks_expected(d, 1) * expert_params(d)
    ops += count(d, "attention") * 4 * d["num_heads"] * d["head_dim"] \
        * context
    ops += count(d, "mamba") * 4 * d["m_heads"] * d["m_head_dim"] * d["state"]
    if head:
        ops += 2 * d["vocab"] * d["d_model"]
    return ops


def prefill_flops(d, span, end):
    """A prefill pass of ``span`` real tokens whose last attends over
    ``end`` positions (each token at the context it really has; one head
    position)."""
    first = end - span
    keys = sum(range(first + 1, end + 1))            # contexts, summed
    return (span * token_flops(d, 0, head=False)
            + count(d, "attention") * 4 * d["num_heads"] * d["head_dim"]
            * keys + 2 * d["vocab"] * d["d_model"])


def window_flops(d, steps):
    """Operations the model needs for every real token the steps decoded
    or prefilled.  A step is ``readers.py``'s tuple: (stamp, requests
    decoded, sum of their contexts, prefill passes [(span, end)], ...)."""
    total = 0
    for s in steps:
        total += s[1] * token_flops(d, 0) + count(d, "attention") * 4 \
            * d["num_heads"] * d["head_dim"] * s[2]
        total += sum(prefill_flops(d, span, end) for span, end in s[3])
    return total


def moe_bytes(d, experts_hit, picks_held, itemsize=2):
    """Bytes the held experts' work has to move: the two matrices of the
    experts hit (summed over layers), and each held pick's row in and out
    at the latent width and its hidden row out and in between the two
    products."""
    return (experts_hit * expert_params(d) * itemsize
            + picks_held * 2 * (d["latent"] + d["expert_ff"]) * itemsize)


def moe_flops(d, picks_held):
    return 2 * picks_held * expert_params(d)


def state_bytes(d):
    """One request's recurrent state over every state-space layer:
    float32 states and the convolution's rows in the activation dtype."""
    di = d["m_heads"] * d["m_head_dim"]
    return count(d, "mamba") * (
        di * d["state"] * 4
        + (d["conv"] - 1) * (di + 2 * d["groups"] * d["state"]) * 2)


def paged_kv_bytes(d, contexts, itemsize=2):
    """Bytes the decode attention has to read: K and V of every attention
    layer over the rows' real contexts, summed."""
    return contexts * 2 * d["kv_heads"] * d["head_dim"] * itemsize \
        * count(d, "attention")


def decode_step_bytes(d, rows, contexts, experts_hit, itemsize=2):
    """Bytes one decode step has to move: every weight that is not a
    routed expert's once (embedding rows aside: one row a token), the
    experts hit, the live requests' states read and written, and the K
    and V the rows' contexts show."""
    fixed = param_count(d) - count(d, "moe") * d["held"] * expert_params(d) \
        - d["vocab"] * d["d_model"]
    return (fixed * itemsize + experts_hit * expert_params(d) * itemsize
            + rows * state_bytes(d) * 2
            + paged_kv_bytes(d, contexts, itemsize))
