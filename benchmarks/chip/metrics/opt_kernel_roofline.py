"""Layer: optimizer kernels. Share of the kernels' roofline: for each
kernel call, the least time the chip could take, max(FLOPs / peak FLOP/s,
required HBM bytes / peak bytes/s), summed over the window and divided
by the kernels' summed device time. FLOPs and bytes come from the
kernel's file under benchmarks/chip/kernels/."""


def read(device, ctx):
    ks = [o for o in device.ops if ctx.is_kernel(o)]
    if not ks:
        return None
    least = 0.0
    for o in ks:
        flops, nbytes = ctx.kernel_cost(o)
        least += max(flops / ctx.peaks["bf16_flops_per_s"],
                     nbytes / ctx.peaks["hbm_bytes_per_s"])
    spent = sum(o.dur for o in ks) / 1e9
    if least <= 0 or spent <= 0:
        return None               # no kernel with a cost file ran
    return 100.0 * least / spent
