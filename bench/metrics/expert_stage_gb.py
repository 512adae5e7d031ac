"""Pipeline staging: GB of held-expert weights staged host-to-device per
cold start, all of them before its first token: the program's staging
counters (``repro.core.staging.COUNTERS``) over every cold start of the
process that staged such weights, set-up's among them. Silent where the
program keeps no such counter or staged no expert."""


def read(rec):
    try:
        from repro.core.staging import COUNTERS
    except ImportError:
        return None
    jobs = COUNTERS.get("expert_stage_jobs", 0)
    return COUNTERS["expert_stage_bytes"] / jobs / 1e9 if jobs else None
