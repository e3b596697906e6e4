"""Device time per round of the cohort store's programs (XLA modules
`jit_store_gather` and `jit_store_scatter`: the cohort's K' rows read,
and written back in place), averaged over the chips, in stretch (D)."""


def read(ctx):
    from tpubench import program_trace as pt

    stretch = ctx.get("stretch_d")
    return None if stretch is None else pt.module_ms(stretch, pt.STORE)
