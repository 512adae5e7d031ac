"""Compile: the share, in percent, of the process's decode servers that
took the decode step already built for their configuration: the program's
counters (``repro.serving.server.COUNTERS``), ``decode_step_reuses`` over
builds plus reuses, set-up's servers among them. Silent where the program
keeps no such counter or built no decode server."""


def read(rec):
    try:
        from repro.serving.server import COUNTERS
    except ImportError:
        return None
    built = COUNTERS.get("decode_step_builds", 0)
    reused = COUNTERS.get("decode_step_reuses", 0)
    return 100.0 * reused / (built + reused) if built + reused else None
