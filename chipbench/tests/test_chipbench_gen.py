"""The benchmark's generator copies give the program's arrays today."""
import numpy as np
import pytest

import _paths
from gen import cluster


@pytest.mark.parametrize("seed,load", [(0, 1.6), (2 ** 31 + 9, 1.0)])
def test_cluster_trace_equals_the_program_generator(seed, load):
    from repro.traces import generate_calibrated

    ours = cluster.generate_calibrated(seed, 60, 48, offered_load=load)
    theirs = generate_calibrated(seed, 60, 48, offered_load=load)
    assert set(ours) == set(theirs._fields)
    for k in ours:
        np.testing.assert_array_equal(ours[k], np.asarray(getattr(theirs, k)))


@pytest.mark.parametrize("mix", ["overload16", "trace10"])
def test_mix_task_count_is_the_calibrated_count_of_seed_0(mix):
    """A mix fixes its task count, so every seed runs one program; the
    count is the calibrated one of seed 0 at the configuration's size."""
    import json

    conf = json.loads((_paths.BENCH / "configs" / "gct2011-4000.json")
                      .read_text())
    m = json.loads((_paths.BENCH / "traffic" / f"{mix}.json").read_text())
    ts = cluster.generate_calibrated(0, conf["n_nodes"], conf["trace_slots"],
                                     offered_load=m["offered_load"])
    assert len(ts["arrival"]) == m["n_tasks"]
