"""SecAgg and aggregation: mean milliseconds per round of the program's
``secagg.encode`` span: the dispatch of the program that encodes the
cohort's payloads as fixed-point field elements and adds their masks on
the device (``_cohort_ciphertexts`` on the ``ideal`` backend)."""


def read(run):
    durs = [ev["dur"] for ev in run.spans if ev["name"] == "secagg.encode"]
    return 1e3 * sum(durs) / len(durs) if durs else None
