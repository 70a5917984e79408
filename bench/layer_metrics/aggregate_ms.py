"""SecAgg and aggregation: mean milliseconds per round of the program's
``aggregate`` span (secure or plain sum, division by the aggregate batch,
the SGD update)."""


def read(run):
    durs = [ev["dur"] for ev in run.spans if ev["name"] == "aggregate"]
    return 1e3 * sum(durs) / len(durs) if durs else None
