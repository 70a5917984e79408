"""SecAgg and aggregation: mean milliseconds per round of the program's
``secagg.sum`` span (the ciphertexts' check, stack and field sum, the
decode, and the sum's transfer back to the device)."""


def read(run):
    durs = [ev["dur"] for ev in run.spans if ev["name"] == "secagg.sum"]
    return 1e3 * sum(durs) / len(durs) if durs else None
