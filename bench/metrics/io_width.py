"""Storage / IO stage: the IO gate's limit at the run's end, from
``stage_stats()["io_width"]``: where the program sizes its IO width from
observed GET latency, how many GETs it keeps in flight once it has settled.
A program without that counter reads nothing."""


def read(run):
    width = run.stage_stats.get("io_width")
    if not width:
        return None
    return width["limit"]
