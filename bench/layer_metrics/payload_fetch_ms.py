"""Fused round step: mean milliseconds per round of the program's
``fused_round.fetch`` span: the wait for the cohort program and the fetch
of its losses; on the ``ideal`` backend the payload stack stays on the
device, SecAgg on or off."""


def read(run):
    durs = [ev["dur"] for ev in run.spans if ev["name"] == "fused_round.fetch"]
    return 1e3 * sum(durs) / len(durs) if durs else None
