"""galore_kernel_ms_per_step: device time of the fused GaLore kernels'
custom calls, per optimizer step (layer: kernels)."""


def read(ctx):
    calls = ctx.trace.get("kernels") or []
    s = sum(k["seconds"] for k in calls)
    return None if not s else 1e3 * s / ctx.steps
