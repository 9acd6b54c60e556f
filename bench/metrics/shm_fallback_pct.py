"""Transport: the share of samples that fell back from the shared-memory
slabs to the pipe over the run, from ``stage_stats()["transport"]``."""


def read(run):
    tr = run.stage_stats.get("transport")
    if not tr:
        return None
    samples = tr["shm_samples"] + tr["pipe_samples"]
    if not samples:
        return None
    return 100.0 * sum(tr["fallbacks"].values()) / samples
