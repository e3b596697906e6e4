"""Mean per round of the program's `client` phase span (blocking), in the
span stretch."""


def read(ctx):
    rounds = ctx["spans"]
    if not rounds:
        return None
    return sum(r["client"] for r in rounds) / len(rounds)
