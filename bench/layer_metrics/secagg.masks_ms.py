"""SecAgg and aggregation: mean milliseconds per round of the program's
``secagg.masks`` span (the pair-pad program on the device and the fetch of
the net-mask matrix to the host)."""


def read(run):
    durs = [ev["dur"] for ev in run.spans if ev["name"] == "secagg.masks"]
    return 1e3 * sum(durs) / len(durs) if durs else None
