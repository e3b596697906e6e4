"""Cohort store time per round: the program's `gather` and `scatter`
phase spans, in the span stretch.  `scatter` times only the submit; with
the device store its device work lands in the next round's `gather`."""


def read(ctx):
    rounds = ctx["spans"]
    if not rounds:
        return None
    return sum(r["gather"] + r["scatter"] for r in rounds) / len(rounds)
