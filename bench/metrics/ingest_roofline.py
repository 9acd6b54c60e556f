"""Ingest kernel: its share of the memory roofline. The bytes the algorithm
needs (one uint8 read and one float32 written per pixel, bench/flops.py)
over the chip's HBM bandwidth, divided by the kernel's device time in the
trace: the Pallas call inside each execution of the ingest program."""
INGEST_PROGRAM = r"ingest"
KERNEL_OP = r"ingest_kernel|pallas|custom"


def read(run):
    trace = run.device
    if trace is None:
        return None
    shares = []
    for dev in trace.devices:
        runs = trace.module_runs(INGEST_PROGRAM, dev)
        kernel_s = trace.op_time_in(runs, dev, KERNEL_OP)
        if not runs or kernel_s <= 0:
            continue
        need = len(runs) * run.images_per_step / run.chips * run.ingest_bytes_per_image()
        shares.append(need / run.peaks["hbm_bytes_per_s"] / kernel_s)
    return 100.0 * sum(shares) / len(shares) if shares else None
