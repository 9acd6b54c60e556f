import os
import subprocess
import sys

import pytest

from bench import spec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELLS = [w["name"] for w in spec.load_benchmark()["workloads"]]


@pytest.fixture(scope="session")
def cpu_env(tmp_path_factory):
    """Environment for a CPU rehearsal in a child process: the CPU backend, a
    compilation cache and object pool of the test's own."""
    tmp = tmp_path_factory.mktemp("bench")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp / "jax_cache"))
    env.pop("XLA_FLAGS", None)
    return env, str(tmp / "pool")


def bench_run(env, pool_dir, *args, timeout=600):
    cmd = [sys.executable, os.path.join(ROOT, "bench", "run.py"), *args]
    if "--rehearse" in args:
        cmd += ["--pool-dir", pool_dir]
    return subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=timeout)
