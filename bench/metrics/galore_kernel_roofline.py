"""galore_kernel_roofline: the least time the chip could take for the
fused GaLore kernel calls over the time they took, in % (layer: kernels).
Each call's least time is max(matrix FLOPs / bf16 peak, HBM bytes / HBM
bandwidth), with FLOPs and bytes from bench/counts.py for the call's
shapes; in these cells the bytes bound it."""
from bench import counts


def read(ctx):
    calls = ctx.trace.get("kernels") or []
    spent = sum(k["seconds"] for k in calls)
    if not spent:
        return None
    least = 0.0
    for k in calls:
        side, L, m, n, r = k["call"]
        flops, nbytes = counts.galore_kernel(side, L, m, n, r)
        least += k["count"] * max(flops / ctx.peaks["bf16_flops_per_s"],
                                  nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / spent
