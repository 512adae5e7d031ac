"""Compile: mean over the window's requests of the time in which at least
one of the request's ``compile`` spans ran: JAX's tracing, lowering and
backend compiles (persistent-cache loads included) on the request's
threads, and the executable cache's lookups (program span)."""
import program_spans


def read(rec):
    return program_spans.mean_busy(rec, "compile")
