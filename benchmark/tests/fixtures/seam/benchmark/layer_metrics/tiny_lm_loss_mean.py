"""Mean final loss of the models trained in the window (a fixture's reader)."""


def read(run):
    print("info tiny_lm reader")
    values = [-f for u in run["units"] for f in u["fitness"]]
    return sum(values) / len(values) if values else None
