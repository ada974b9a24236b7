"""step_mfu: the whole train step's model FLOPs per second over the chips'
bf16 peak, in % (layer: train step). Model FLOPs per token from
bench/counts.py (6 per matmul weight plus attention, no recomputation),
times the traced window's tokens per second."""
from bench import counts


def read(ctx):
    if not ctx.trace:
        return None
    run = ctx.run
    per_token = counts.model_flops_per_token(ctx.cell.config,
                                             int(ctx.cell.traffic["seq_len"]))
    rate = run["tokens"] / run["window_s"]
    return 100.0 * per_token * rate / (ctx.n_chips * ctx.peaks["bf16_flops_per_s"])
