"""Round schedule: mean milliseconds per round of the program's
``account`` span (the accountant's step, the privacy ledger's append and
the round log's epsilon)."""


def read(run):
    durs = [ev["dur"] for ev in run.spans if ev["name"] == "account"]
    return 1e3 * sum(durs) / len(durs) if durs else None
