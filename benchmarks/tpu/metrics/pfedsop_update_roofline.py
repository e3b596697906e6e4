"""The pFedSOP round-start update's share of its roofline, in the profiled
stretch: the least time the chip could take for the update's work
(max of bytes over HBM bandwidth and FLOPs over the bf16 peak, once per
round) over the device time of the kernel's own operations, the Pallas
calls named `pfedsop_update*` (the `pfedsop_update[...]` name scope of
the program does not reach the device trace).  Nothing to read where no
such operation ran."""

KERNEL = "%pfedsop_update"


def read(ctx):
    from tpubench.profile import ops_s

    kernel_s = ops_s(ctx["events"], KERNEL, ctx["lo"], ctx["hi"])
    if kernel_s <= 0:
        return None
    flops, nbytes = ctx["update_work"]
    peak = ctx["peak"]
    least = ctx["rounds"] * max(nbytes / peak["hbm_bytes_per_s"],
                                flops / peak["bf16_flops"])
    return 100.0 * least / kernel_s
