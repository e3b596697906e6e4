"""Whole-round model FLOP utilisation in the profiled stretch: model FLOPs
of the rounds completed (local SGD at 3x forward, eval forward over the
real test samples) over stretch time x chips x the chip's bf16 peak."""


def read(ctx):
    if ctx["window_s"] <= 0:
        return None
    return 100.0 * ctx["model_flops"] / (ctx["window_s"] * ctx["chips"]
                                         * ctx["peak"]["bf16_flops"])
