"""Train step: tokens a second a chip in the traced steps x FLOPs a token
(work.train_flops_per_token: 6 x matmul parameters, causal attention
halved, recomputation not counted) over the chip's bf16 peak."""
from benchmark import work


def read(ctx):
    if not ctx["peaks"]:
        return None
    tok_s_chip = ctx["steps"] * ctx["tokens_per_step"] / ctx["window_s"] / ctx["chips"]
    return 100.0 * tok_s_chip * work.train_flops_per_token(ctx["model"], ctx["seq"]) \
        / ctx["peaks"]["bf16_flops"]
