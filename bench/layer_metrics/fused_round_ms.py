"""Fused round step: mean milliseconds per round of the program's
``fused_round`` span (host draw, dispatch, and the wait for the cohort
program and the fetch of its losses)."""


def read(run):
    durs = [ev["dur"] for ev in run.spans if ev["name"] == "fused_round"]
    return 1e3 * sum(durs) / len(durs) if durs else None
