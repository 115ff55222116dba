"""Kernel `kernels/bidir_linear_attention.py` on queries and keys of
different lengths (PVT's spatial-reduction attention): roofline share of its
device time in the traced window. Its calls per forward are the family's
`cost.kernel_calls(...)["bidir_attn_kv"]`, (g, n_q, n_kv, dk, dv) each; its
device events are the HLO instructions named after the jitted
`bidir_binary_attention_pallas`."""
from bench.lib.context import kernel_roofline

PATTERN = r"^bidir_binary_attention_pallas(\.\d+)?$"


def reduced_attn_cost(g: int, n_q: int, n_kv: int, dk: int, dv: int) -> tuple:
    """(ops, bytes) of fused binary linear attention of n_q queries against
    n_kv keys over g (batch x head) groups: k^T v over the keys and
    q (k^T v) over the queries at two operations a term, the key and value
    sums over the keys, the normaliser and its division over the queries; q
    and the output (n_q rows), k and v (n_kv rows) read or written once, in
    f32. With n_q = n_kv it is `bench.lib.cost.bidir_attn_cost`."""
    ops = g * (2.0 * (n_kv + n_q) * dk * dv + (n_kv + n_q) * (dk + dv))
    return ops, 4.0 * g * (n_q + n_kv) * (dk + dv)


def read(ctx):
    def calls(cfg, batch):
        return ctx.family.cost.kernel_calls(cfg, batch).get("bidir_attn_kv",
                                                            [])
    return kernel_roofline(ctx, PATTERN, calls, reduced_attn_cost)
