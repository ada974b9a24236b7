"""model_ms_per_step: device time of the ops whose HLO source lies under
src/repro/models/, per optimizer step (layer: model)."""


def read(ctx):
    s = ctx.trace.get("layers", {}).get("model")
    return None if not s else 1e3 * s / ctx.steps
