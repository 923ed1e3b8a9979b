"""Host milliseconds per ``union.run`` call outside the device run: the
facade, the planner, member set-up and the reports (span ``union.run``
less span ``engine.run``, over the calls)."""


def read(ctx):
    runs = [e - s for n, s, e in ctx["spans"] if n == "union.run"]
    if not runs:
        return None
    dev = sum(e - s for n, s, e in ctx["spans"] if n == "engine.run")
    return (sum(runs) - dev) / len(runs) * 1e-6
