"""SecAgg and aggregation: mean milliseconds per round of the program's
``secagg.sum`` span: the uploads' check, any Shamir reconstruction and
recovery pads, and the dispatch of the program that sums and decodes the
ciphertexts on the device."""


def read(run):
    durs = [ev["dur"] for ev in run.spans if ev["name"] == "secagg.sum"]
    return 1e3 * sum(durs) / len(durs) if durs else None
