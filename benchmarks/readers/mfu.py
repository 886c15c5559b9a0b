"""Model FLOP/s utilization: FLOPs a token requires (a shapes function that
the configuration's family module holds) times the tokens per second per
chip the window measured, over the chip's published bf16 peak. In percent."""


def read(ctx, flops_fn: str, rate: str):
    tok_s = ctx.counters.get(rate)
    if tok_s is None:
        return None
    flops = getattr(ctx.family, flops_fn)(ctx.config["model"], ctx.traffic["seq_len"])
    return 100.0 * flops * tok_s / ctx.peaks["bf16_flops"]
