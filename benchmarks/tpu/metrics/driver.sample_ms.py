"""Host time per round of the program's `sample` span (client draw,
batch and test-set gathers in numpy), in stretch (D)."""


def read(ctx):
    from tpubench import program_trace as pt

    stretch = pt.ensure(ctx)
    return None if stretch is None else pt.span_ms(stretch, "sample")
