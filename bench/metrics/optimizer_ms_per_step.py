"""optimizer_ms_per_step: device time of the ops whose HLO source lies
under src/repro/core/, optim/ or quant/, per optimizer step; the Pallas
kernels are not in it (layer: GaLore optimizer)."""


def read(ctx):
    s = ctx.trace.get("layers", {}).get("optimizer")
    return None if not s else 1e3 * s / ctx.steps
