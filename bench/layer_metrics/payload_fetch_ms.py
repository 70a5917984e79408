"""Fused round step: mean milliseconds per round of the program's
``fused_round.fetch`` span (the wait for the cohort program and the one
transfer of its losses, and with SecAgg on its payload stack, to the
host)."""


def read(run):
    durs = [ev["dur"] for ev in run.spans if ev["name"] == "fused_round.fetch"]
    return 1e3 * sum(durs) / len(durs) if durs else None
