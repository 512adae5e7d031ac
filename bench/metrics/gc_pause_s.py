"""Host process: mean over the window's requests of the time the garbage
collector ran between the request's issue and its last token (the
program's ``gc`` spans, from ``gc.callbacks``)."""
import program_spans


def read(rec):
    return program_spans.mean_busy(rec, "gc", within_request=True)
