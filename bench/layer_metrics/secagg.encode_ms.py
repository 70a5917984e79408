"""SecAgg and aggregation: mean milliseconds per round of the program's
``secagg.encode`` span (the cohort's payloads to fixed-point field
elements, flattened, on the host)."""


def read(run):
    durs = [ev["dur"] for ev in run.spans if ev["name"] == "secagg.encode"]
    return 1e3 * sum(durs) / len(durs) if durs else None
