"""Operations and bytes of a block-diffusion decoder with routed experts
(SDAR), kept with the benchmark so that a later change to the program
cannot move the yardstick.  ``d`` is ``diffusion_cell.dims(cfg)``.  One
multiply-accumulate counts as 2 operations; elementwise, normalisation,
rotary, routing, sorting and unmasking work counts as free.  Every layer
is attention plus a routed block of ``num_experts`` SwiGLU experts, all
held on this chip, and no shared expert.

A denoise pass computes every position of every row's block: R rows are
``R * block`` positions, each attending over its row's context (the
committed positions and its own block).
"""


def attn_params(d):
    """Matrix parameters of one attention layer: q, k, v, output."""
    D, Dh, H, Hkv = d["d_model"], d["head_dim"], d["num_heads"], d["kv_heads"]
    return (H + 2 * Hkv) * Dh * D + D * H * Dh


def expert_params(d):
    """One routed expert: up-gate and down."""
    return 3 * d["d_model"] * d["expert_ff"]


def layer_fixed_params(d):
    """Matrix parameters every position of a layer is multiplied with:
    attention and the router."""
    return attn_params(d) + d["num_experts"] * d["d_model"]


def param_count(d):
    """Every parameter this program holds: embedding and head, and per
    layer attention, router, experts and the norms (two of D, two of
    head_dim)."""
    D = d["d_model"]
    per = (layer_fixed_params(d) + d["num_experts"] * expert_params(d)
           + 2 * D + 2 * d["head_dim"])
    return 2 * d["vocab"] * D + D + d["layers"] * per


def kv_bytes_per_position(d, itemsize=2):
    """K and V of one position, every layer."""
    return d["layers"] * 2 * d["kv_heads"] * d["head_dim"] * itemsize


def position_flops(d, context, head=True):
    """Operations for ONE position that attends over ``context``
    positions: every layer's attention and router, ``top_k`` experts, the
    scores and values over the context, and the head."""
    ops = d["layers"] * (2 * layer_fixed_params(d)
                         + 2 * d["top_k"] * expert_params(d)
                         + 4 * d["num_heads"] * d["head_dim"] * context)
    if head:
        ops += 2 * d["vocab"] * d["d_model"]
    return ops


def denoise_flops(d, rows, contexts):
    """A denoise (or store) pass of ``rows`` rows whose paged queries
    attend over ``contexts`` positions in all (each row's committed
    length plus its block): every block position's operations."""
    B = d["block"]
    per = position_flops(d, 0)
    return rows * B * per + d["layers"] * 4 * d["num_heads"] \
        * d["head_dim"] * B * contexts


def prefill_flops(d, span, end):
    """A prefill or chunk pass of ``span`` positions ending at ``end``
    under the block-causal mask (a position sees its whole block), the
    head on one position."""
    B = d["block"]
    first = end - span
    keys = sum(min((p // B + 1) * B, end) for p in range(first, end))
    return (span * position_flops(d, 0, head=False)
            + d["layers"] * 4 * d["num_heads"] * d["head_dim"] * keys
            + 2 * d["vocab"] * d["d_model"])


def paged_bytes(d, contexts, itemsize=2):
    """Bytes the paged attention of a denoise pass has to read: K and V
    of every layer over the positions its rows attend to."""
    return contexts * kv_bytes_per_position(d, itemsize)


def denoise_bytes(d, rows, contexts, experts_hit, itemsize=2):
    """Bytes one denoise pass has to move: every weight that is not an
    expert's once (embedding rows aside: one a position), the experts hit
    (summed over the layers), the K and V the rows attend to, and the
    K and V the pass writes (a block a row, every layer)."""
    fixed = (param_count(d)
             - d["layers"] * d["num_experts"] * expert_params(d)
             - d["vocab"] * d["d_model"])
    written = rows * d["block"] * kv_bytes_per_position(d, itemsize)
    return (fixed * itemsize + experts_hit * expert_params(d) * itemsize
            + paged_bytes(d, contexts, itemsize) + written)
