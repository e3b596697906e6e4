"""Device time per round of the cohort store's programs (XLA modules
`jit_store_gather` and `jit_store_scatter`, the whole-stack write-back
included), averaged over the chips, in stretch (D)."""


def read(ctx):
    from tpubench import program_trace as pt

    stretch = pt.ensure(ctx)
    return None if stretch is None else pt.module_ms(stretch, pt.STORE)
