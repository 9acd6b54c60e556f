"""Model families: what a cell's samples, program and references are.

A configuration's ``family`` key names a module ``<family>.py`` here, which
``bench.spec.family`` loads by its path as it loads a metric's reader. The
harness (``bench/harness.py``) owns the store, the loader, the window, the
trace and the result line; a family module gives it the rest:

``PREFIX``
    the prefix of the keys its dataset reads from the store.
``load_pool(objects, pool_dir) -> bench.storage.Pool``
    the objects behind the keyspace, from the traffic file's ``objects``.
``rehearse(config, objects) -> (config, objects)``
    the same at tiny sizes, for a CPU rehearsal.
``samples_per_step(config) -> int``
    the samples in one step's batch (``Run.images_per_step`` counts them).
``dataset(config, traffic, store, seed, tracer, fault) -> dataset``
    the program's dataset over the store; ``fault`` is ``""`` or a planted
    fault's name (``bench/faults.py``).
``init_state(config, seed) -> state``
    the program's training state from the seed, made on the device in one
    jitted call; ``state["params"]`` and ``state["opt"]["mu"]`` are read by
    the check.
``make_step(config) -> train_step(state, batch) -> (state, metrics)``
    the program's step; ``metrics["loss"]`` is read.
``trainer_options(config, traffic) -> dict``
    further keyword arguments of the program's ``Trainer`` (a device
    epilogue), or ``{}``.
``warm_batch(config, options) -> batch``
    a batch of zeros as the step receives it, on the device, made with the
    ``trainer_options``: one warm-up step compiles every program the window
    runs.
``keep(batch) -> object``
    a host copy of what the check needs of one of the first steps' batches.
``reference(config, traffic, pool, seed) -> dict``
    the plain references' first ``bench.check.CHECKED_STEPS`` steps:
    ``batches`` (as ``keep`` gives them), ``losses``, ``g1``, ``p0``, ``p3``
    (see ``bench.check.train_numbers``) and whatever ``compare`` reads.
``compare(prog, ref, config) -> {number: value}``
    the numbers that the limits file compares; ``prog`` holds the
    program's ``batches``, ``losses``, ``mu1``, ``p0`` and ``p3``.
``flops_per_sample(config) -> float``
    the training FLOPs one sample needs (``Run.flops_per_image``).
``ingest_bytes_per_sample(config) -> float``
    the bytes the device epilogue moves per sample.
``readings(config, seed, ref, prog) -> dict``
    for ``bench/calibrate.py``: ``compare`` of the program and of each
    control and planted fault, from which the limits are set.
"""
