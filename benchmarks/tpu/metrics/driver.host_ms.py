"""Host time of the driver per round: the benchmark's round span minus
the program's phase spans inside it (host sampling, test-set gathers,
launches), in the span stretch."""

PHASES = ("gather", "client", "all_gather", "eval", "aggregate", "scatter")


def read(ctx):
    rounds = ctx["spans"]
    if not rounds:
        return None
    return sum(r["round"] - sum(r[p] for p in PHASES) for r in rounds) / len(rounds)
