"""SecAgg and aggregation: mean milliseconds per round of the program's
``update`` span (division by the aggregate batch, the SGD step's
dispatch, the mean loss)."""


def read(run):
    durs = [ev["dur"] for ev in run.spans if ev["name"] == "update"]
    return 1e3 * sum(durs) / len(durs) if durs else None
