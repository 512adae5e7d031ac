"""Decode: mean host work of the program's ``decode.pick`` span per decoded
token, in ms: the logits row sliced, the sampling key split and the
sampling dispatched. The ``decode.wait`` spans inside the picks, where the
host waits for the device's token id, are left out (program span)."""
import program_spans


def read(rec):
    reqs = program_spans.requests(rec)
    picks = [e - s for r in reqs for s, e in r["spans"].get("decode.pick", ())]
    waits = [e - s for r in reqs for s, e in r["spans"].get("decode.wait", ())]
    if not picks:
        return None
    return 1000.0 * (sum(picks) - sum(waits)) / len(picks)
