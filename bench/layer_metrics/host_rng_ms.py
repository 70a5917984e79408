"""Round schedule: mean host milliseconds per round of the Poisson draw and
stacking of the cohort's batch (the program's ``host_rng.stack_poisson``
span)."""


def read(run):
    durs = [ev["dur"] for ev in run.spans
            if ev["name"] == "host_rng.stack_poisson"]
    return 1e3 * sum(durs) / len(durs) if durs else None
