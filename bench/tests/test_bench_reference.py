"""The vectorised references against the plain per-op ones, over several
seeds of each cell's traffic mix at tiny sizes, near-empty queues and
ops that are not valid included."""
import numpy as np
import pytest

from tiny import cell_named, cells, tiny
from bench.harness import Layout
from bench.traffic import Stream

LAYOUT = Layout()
CELLS = [w["name"] for w in LAYOUT.spec["workloads"]] + cells(LAYOUT)[-1:]


@pytest.mark.parametrize("share", [0.3, 0.5, 0.7])
@pytest.mark.parametrize("seed", [0, 1, 2**31 + 7])
@pytest.mark.parametrize("cell", CELLS)
def test_replay_equals_plain(cell, seed, share):
    c = tiny(cell_named(LAYOUT, cell))
    tiers = c.config["tiers"]
    plain = c.reference.Plain(tiers)
    fast = c.reference.Replay(tiers, 1 << 16)
    stream = Stream(dict(c.mix, loop="closed", enqueue_share=share), seed,
                    tiers)
    rng = np.random.default_rng(seed)
    K, N, next_id, served = 4, 32, 0, 0
    for _ in range(25):
        is_enq, key, _ = stream.take(K * N)
        is_enq, key = is_enq.reshape(K, N), key.reshape(K, N)
        valid = rng.random((K, N)) < 0.9
        e = is_enq & valid
        ids = np.full((K, N), -1)
        ids[e] = next_id + np.arange(e.sum())
        next_id += int(e.sum())
        want, got = plain.burst(is_enq, valid, key, ids), \
            fast.burst(is_enq, valid, key, ids)
        assert want.keys() == got.keys()
        for f in want:
            np.testing.assert_array_equal(got[f], want[f], err_msg=f)
        served += int((want["value"] >= 0).sum())
    assert served > 0
