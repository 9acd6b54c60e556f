"""The harness finds a configuration's family by its ``family`` key, and a
family other than ResNet (a toy token family, ``fixtures/toy_lm/``) runs
through it from new files alone."""
import json
import os
import shutil

import pytest

from bench import spec
from bench.tests.conftest import bench_run

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures", "toy_lm")
TOY = "toy_lm.tokens"


def test_resnet18_resolves_to_the_resnet_family():
    cell = spec.resolve("rn18.s3", spec.load_benchmark())
    assert cell.config["family"] == "resnet"
    assert cell.family.__file__ == os.path.join(spec.FAMILIES, "resnet.py")


@pytest.mark.parametrize("name,named", [(None, "'family' key"), ("", "'family' key"),
                                        ("no_such_family", "'no_such_family'")])
def test_a_missing_or_unknown_family_fails_naming_it(name, named):
    with pytest.raises(KeyError) as e:
        spec.family(name)
    assert named in str(e.value)


@pytest.mark.parametrize("family", [None, "no_such_family"])
def test_a_cell_whose_config_lacks_its_family_does_not_resolve(tmp_path, family):
    shutil.copytree(FIXTURE, tmp_path, dirs_exist_ok=True)
    path = tmp_path / "bench" / "configs" / "toy_lm.json"
    config = json.loads(path.read_text())
    if family is None:
        del config["family"]
    else:
        config["family"] = family
    path.write_text(json.dumps(config))
    bench = spec.load_benchmark(str(tmp_path))
    with pytest.raises(KeyError) as e:
        spec.resolve(TOY, bench, str(tmp_path))
    assert ("'family' key" if family is None else repr(family)) in str(e.value)


def test_the_fixture_family_is_found_before_the_benchmarks():
    """A root is a whole checkout: its families are looked up there alone."""
    cell = spec.resolve(TOY, spec.load_benchmark(FIXTURE), FIXTURE)
    assert cell.family.__file__ == os.path.join(FIXTURE, "bench", "families", "toy_lm.py")
    with pytest.raises(KeyError):
        spec.family("resnet", os.path.join(FIXTURE, "bench", "families"))
    assert cell.family.samples_per_step(cell.config) == cell.config["batch_per_chip"]


def _toy_run(cpu_env, *args):
    env, pool = cpu_env
    p = bench_run(env, pool, "--workload", TOY, "--seed", str(2**31 + 4242), "--seconds", "2",
                  "--rehearse", "--root", FIXTURE, *args)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("trace", ["0", "1"])
def test_toy_token_family_runs_to_correct(cpu_env, trace):
    line = _toy_run(cpu_env, "--trace", trace)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["checks"]) == {"batch_mismatch", "loss_gap"}
    if trace == "0":
        assert set(line["metrics"]) == {"train_images_per_s", "step_p90_ms", "setup_s"}
    else:
        # the CPU stage's spans, taken by the program for a token dataset too
        assert line["metrics"]["cpu_ms_per_img"]["value"] > 0


def test_toy_token_family_on_half_a_batch_reads_not_correct(cpu_env):
    line = _toy_run(cpu_env, "--trace", "0", "--fault", "half")
    assert line["correct"] is False
    assert line["checks"]["loss_gap"]["value"] > line["checks"]["loss_gap"]["limit"]
