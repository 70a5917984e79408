"""Fused round step: mean milliseconds per round of the program's
``fused_round`` span (host draw, dispatch, host sync, and with SecAgg on the
transfer of the payload stack to the host)."""


def read(run):
    durs = [ev["dur"] for ev in run.spans if ev["name"] == "fused_round"]
    return 1e3 * sum(durs) / len(durs) if durs else None
