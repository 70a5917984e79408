"""SecAgg and aggregation: megabytes moved between host and device per
round for SecAgg, the sum of the ``bytes`` arguments of the program's
``fused_round.fetch`` (payloads and losses to the host), ``secagg.masks``
(net masks to the host) and ``secagg.sum`` (the sum to the device) spans,
over the window's ``round`` spans.  A count fixed by the model and the
cohort: it falls when SecAgg work moves onto the device."""

SPANS = ("fused_round.fetch", "secagg.masks", "secagg.sum")


def read(run):
    moved = [ev["args"]["bytes"] for ev in run.spans if ev["name"] in SPANS]
    rounds = sum(ev["name"] == "round" for ev in run.spans)
    if not moved or not rounds:
        return None
    return sum(moved) / rounds / 1e6
