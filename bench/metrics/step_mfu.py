"""step_mfu: the whole train step's model FLOPs per second over the chips'
bf16 peak, in % (layer: train step). Model FLOPs per token from the
architecture's module (bench/arch/; forward and backward, no
recomputation), times the traced window's tokens per second."""
from bench import arch


def read(ctx):
    if not ctx.trace:
        return None
    run = ctx.run
    config = ctx.cell.config
    per_token = arch.of(config).flops_per_token(config,
                                                int(ctx.cell.traffic["seq_len"]))
    rate = run["tokens"] / run["window_s"]
    return 100.0 * per_token * rate / (ctx.n_chips * ctx.peaks["bf16_flops_per_s"])
