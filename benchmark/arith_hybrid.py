"""Operations and bytes of a hybrid decoder (Mamba-2 and attention layers
under one dense SwiGLU block each), kept with the benchmark so that a later
change to the program cannot move the yardstick.  ``d`` is
``hybrid_cell.dims(cfg)``.  One multiply-accumulate counts as 2 operations;
elementwise, normalisation and convolution work counts as free.
"""


def layer_params(d):
    """(parameters of one Mamba layer, of one attention layer), each with
    its SwiGLU block and two norms."""
    D, F = d["d_model"], d["d_ff"]
    di, N, H, K = d["d_inner"], d["state"], d["m_heads"], d["conv"]
    cd = di + 2 * N
    mlp = 2 * F * D + D * F + 2 * D
    mamba = ((di + cd + H) * D + cd * K + cd + 3 * H + di + D * di + mlp)
    attn = ((d["num_heads"] + 2 * d["kv_heads"]) * d["head_dim"] * D
            + D * d["num_heads"] * d["head_dim"] + mlp)
    return mamba, attn


def param_count(d):
    """Every parameter of the model, the tied embedding once."""
    mamba, attn = layer_params(d)
    return (d["n_mamba"] * mamba + d["n_attn"] * attn
            + d["vocab"] * d["d_model"] + d["d_model"])


def matmul_params(d):
    """Matrix parameters a token is multiplied with, the head left out."""
    D, F = d["d_model"], d["d_ff"]
    di, N, H = d["d_inner"], d["state"], d["m_heads"]
    mlp = 3 * F * D
    mamba = (2 * di + 2 * N + H) * D + D * di + mlp
    attn = ((d["num_heads"] + 2 * d["kv_heads"]) * d["head_dim"] * D
            + D * d["num_heads"] * d["head_dim"] + mlp)
    return d["n_mamba"] * mamba + d["n_attn"] * attn


def token_flops(d, context, head=True):
    """Operations for ONE token that attends over ``context`` positions:
    the matrix products, the attention layers' q.k and p.v, the
    state-space layers' state update and read-out (2 operations each per
    state element), and the tied head if the token is sampled."""
    ops = 2 * matmul_params(d)
    ops += d["n_attn"] * 4 * d["num_heads"] * d["head_dim"] * int(context)
    ops += d["n_mamba"] * 4 * d["d_inner"] * d["state"]
    if head:
        ops += 2 * d["vocab"] * d["d_model"]
    return ops


def prefill_flops(d, span, end):
    """A prefill pass of ``span`` real tokens whose last attends over
    ``end`` positions (each token is counted at the context it really
    has; one head position)."""
    first = end - span
    attn = sum(range(first + 1, end + 1))            # contexts, summed
    return (span * token_flops(d, 0, head=False)
            + d["n_attn"] * 4 * d["num_heads"] * d["head_dim"] * attn
            + 2 * d["vocab"] * d["d_model"])


def window_flops(d, steps):
    """Operations the model needs for every real token the steps decoded
    or prefilled.  A step is ``readers.py``'s tuple: (stamp, requests
    decoded, sum of their contexts, prefill passes [(span, end)], ...)."""
    total = 0
    for s in steps:
        total += s[1] * token_flops(d, 0) \
            + d["n_attn"] * 4 * d["num_heads"] * d["head_dim"] * s[2]
        total += sum(prefill_flops(d, span, end) for span, end in s[3])
    return total


def state_bytes(d):
    """One request's recurrent state over every state-space layer:
    float32 states and the convolution's rows in the activation dtype."""
    return d["n_mamba"] * (d["m_heads"] * d["m_head_dim"] * d["state"] * 4
                           + (d["conv"] - 1) * (d["d_inner"]
                                                + 2 * d["state"]) * 2)


def ssm_update_bytes(d, rows):
    """Bytes the single-token state update has to move for ``rows`` live
    requests: every float32 state read once and written once."""
    return rows * d["n_mamba"] * d["m_heads"] * d["m_head_dim"] \
        * d["state"] * 4 * 2


def paged_kv_bytes(d, contexts, itemsize=2):
    """Bytes the decode attention has to read: the K and V of every
    attention layer over the rows' real contexts, summed."""
    return int(contexts) * 2 * d["kv_heads"] * d["head_dim"] * itemsize \
        * d["n_attn"]


def decode_step_bytes(d, rows, contexts, itemsize=2):
    """Bytes one decode step has to move: every weight once (the tied
    embedding once, as the head), the live requests' states read and
    written, the K and V of the attention layers over the real contexts."""
    weights = param_count(d) * itemsize
    return (weights + rows * state_bytes(d) * 2
            + paged_kv_bytes(d, contexts, itemsize))
