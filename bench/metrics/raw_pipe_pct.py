"""Transport, parent -> worker: the share of tasks whose raw bytes went to a
CPU worker in the task message through the pipe, not through its
shared-memory input ring, from ``stage_stats()["transport"]``. A program
without the input ring's counters reads nothing."""


def read(run):
    tr = run.stage_stats.get("transport")
    if not tr or "raw_pipe_samples" not in tr:
        return None
    sent = tr["raw_shm_samples"] + tr["raw_pipe_samples"]
    if not sent:
        return None
    return 100.0 * tr["raw_pipe_samples"] / sent
