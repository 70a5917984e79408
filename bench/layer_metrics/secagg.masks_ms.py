"""SecAgg and aggregation: mean milliseconds per round of the program's
``secagg.masks`` span: the dispatch of the pair-pad program; the net
masks stay on the device."""


def read(run):
    durs = [ev["dur"] for ev in run.spans if ev["name"] == "secagg.masks"]
    return 1e3 * sum(durs) / len(durs) if durs else None
