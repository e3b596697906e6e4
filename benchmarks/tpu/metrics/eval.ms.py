"""Mean per round of the program's `eval` phase span (blocking), in the
span stretch."""


def read(ctx):
    rounds = ctx["spans"]
    if not rounds:
        return None
    return sum(r["eval"] for r in rounds) / len(rounds)
