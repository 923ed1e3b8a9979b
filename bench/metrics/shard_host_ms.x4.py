"""Host milliseconds a ``union.run`` call spends placing the stacked
members on the member mesh and splitting the final state back into
members (span ``engine.shard``), over the calls. A program without the
span gives nothing."""


def read(ctx):
    calls = sum(1 for n, s, e in ctx["spans"] if n == "union.run")
    shard = [e - s for n, s, e in ctx["spans"] if n == "engine.shard"]
    if not calls or not shard:
        return None
    return sum(shard) / calls * 1e-6
