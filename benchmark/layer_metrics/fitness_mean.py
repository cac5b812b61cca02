"""Mean validation accuracy of the individuals trained in the window.  Not an
end-to-end metric: after 19 steps it moves by a tenth from seed to seed (PERF.md)."""


def read(run):
    values = [f for u in run["units"] for f in u["fitness"]]
    return sum(values) / len(values) if values else None
