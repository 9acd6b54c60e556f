import statistics

import numpy as np

from bench import storage
from bench.reference import loader as ref_loader

SPEC = {"pool": 8, "pool_seed": 0, "height": 387, "width": 469, "coarse": 8,
        "posterize": 8, "noise_amp": 8, "noise_p": [0.01, 0.05], "zlib_level": 6}


def _s3(seed=7, base=None):
    return storage.S3Model(base, latency_median_s=0.08, latency_sigma=0.5,
                           bandwidth_per_conn=25e6, nic_bandwidth=1.2e9,
                           max_connections=256, seed=seed)


def test_latency_is_a_function_of_seed_key_and_attempt():
    a, b = _s3(seed=7), _s3(seed=7)
    assert a.latency("k1", 0) == b.latency("k1", 0)
    assert a.latency("k1", 0) != a.latency("k1", 1)
    assert a.latency("k1", 0) != a.latency("k2", 0)
    assert a.latency("k1", 0) != _s3(seed=8).latency("k1", 0)


def test_latency_median_and_spread():
    s3 = _s3()
    lat = [s3.latency(f"imagenet/train/{i:08d}.rimg", 0) for i in range(20000)]
    assert abs(statistics.median(lat) - 0.08) < 0.004
    logs = np.log(lat)
    assert abs(logs.std() - 0.5) < 0.02


def test_bandwidth_is_shared_past_the_nic():
    s3 = _s3()
    lat = s3.latency("k", 0)
    assert abs(s3.service_time("k", 0, 115_000, 1) - (lat + 115_000 / 25e6)) < 1e-12
    # 96 transfers at 25 MB/s fill a 1.2 GB/s NIC twice over
    assert abs(s3.service_time("k", 0, 115_000, 96) - (lat + 115_000 * 96 / 1.2e9)) < 1e-12


def test_pool_objects_are_seeded_and_decode(tmp_path):
    pool = storage.load_pool(SPEC, str(tmp_path), workers=1)
    again = storage.load_pool(SPEC, str(tmp_path), workers=1)
    assert len(pool) == 8 and pool.blob == again.blob
    assert storage.make_object(SPEC, 3) == pool.get(3)
    px, label = ref_loader.decode(pool.get(0))
    assert px.shape == (387, 469, 3) and 0 <= label < 1000
    # the paper's ImageNet objects average about 115 KB
    assert 90e3 < pool.mean_size() < 140e3


def test_keys_map_onto_the_pool_by_seed(tmp_path):
    pool = storage.load_pool(dict(SPEC, height=16, width=16, coarse=4), str(tmp_path), workers=1)
    a = storage.PoolStore(pool, 1000, seed=1)
    key = "imagenet/train/00000042.rimg"
    assert a.get(key) == storage.PoolStore(pool, 1000, seed=1).get(key)
    assert len({a.slot(i) for i in range(1000)}) == len(pool)
    assert [a.slot(i) for i in range(64)] != [storage.PoolStore(pool, 1000, seed=2).slot(i)
                                              for i in range(64)]
