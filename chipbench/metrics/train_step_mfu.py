"""The whole training step's share of the chip's bf16 peak: the FLOPs the
forward and backward passes require per token (``chipbench.flops``: 6 per
matmul parameter plus attention's causal products, nothing recomputed)
times the window's tokens per second, over the peak, in %."""
from chipbench.flops import dense_train_flops_per_token


def read(ctx):
    c = ctx.counters
    if not c.get("steps") or ctx.peaks is None:
        return None
    tokens_per_s = c["steps"] * c["tokens_per_step"] / c["window_s"]
    flops = dense_train_flops_per_token(ctx.config, c["seq_len"])
    return 100.0 * flops * tokens_per_s / ctx.peaks["bf16_flops_per_s"]
