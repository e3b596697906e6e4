"""Device time per round of the client program (XLA module
`jit_client_round`), averaged over the chips, in stretch (D)."""


def read(ctx):
    from tpubench import program_trace as pt

    stretch = ctx.get("stretch_d")
    return None if stretch is None else pt.module_ms(stretch, pt.CLIENT)
