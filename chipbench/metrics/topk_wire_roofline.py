"""Kernel: the `kernels/topk_wire` Pallas kernel's share of its
roofline. The least time of one call is the larger of its operations
over the peak FLOP/s and its bytes over the peak HBM bandwidth
(`chipbench.counts`, from shapes alone); the share is that least time,
times the calls, over the kernel's device time in the trace."""

from chipbench import counts

KERNEL = "topk_wire"  # the Pallas call's instruction in the trace


def read(ctx):
    seconds = ctx.reduction.kernel_seconds(KERNEL)
    calls = ctx.span_count("publish/encode")
    if seconds <= 0 or calls == 0 or ctx.peaks is None:
        return None
    rows, vocab = ctx.wire_rows
    k = ctx.cfg["wire"]["topk"]
    least = max(counts.topk_wire_ops(rows, vocab) / ctx.peaks["flops"],
                counts.topk_wire_bytes(rows, vocab, k)
                / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least * calls / seconds
