"""Device time per round of the eval program (XLA module
`jit_eval_round`), averaged over the chips, in stretch (D)."""


def read(ctx):
    from tpubench import program_trace as pt

    stretch = pt.ensure(ctx)
    return None if stretch is None else pt.module_ms(stretch, pt.EVAL)
