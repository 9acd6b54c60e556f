"""Train step: device time of the jitted step's operations per step, in
milliseconds, from the trace (averaged over the chips)."""
STEP_PROGRAM = r"train_step"


def read(run):
    trace = run.device
    if trace is None:
        return None
    per = []
    for dev in trace.devices:
        runs = trace.module_runs(STEP_PROGRAM, dev)
        if runs:
            per.append(trace.op_time_in(runs, dev) / len(runs))
    return 1e3 * sum(per) / len(per) if per else None
