"""The hybrid training step's share of the host's bf16 peak: the FLOPs the
forward and backward passes require per token (``chipbench.flops_hybrid``:
6 per matmul parameter with the held experts at their expected share,
plus attention's causal and the scan's chunked products, nothing
recomputed) times the window's tokens per second, over the chips' summed
peak, in %."""
from chipbench.flops_hybrid import hybrid_train_flops_per_token


def read(ctx):
    c = ctx.counters
    if not c.get("steps") or not c.get("chips") or ctx.peaks is None:
        return None
    tokens_per_s = c["steps"] * c["tokens_per_step"] / c["window_s"]
    flops = hybrid_train_flops_per_token(ctx.config, c["seq_len"])
    return 100.0 * flops * tokens_per_s / (c["chips"] * ctx.peaks["bf16_flops_per_s"])
