"""The chip smoke's phases, run on the CPU at tiny sizes.

``chip_smoke.py`` drives the same functions on the chip at deployment
size; here they run with a store of a few thousand records of 8 words, so
their traffic, references and checks are exercised on every test run.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

from multidev import run_multidev
from repro.configs import get_config
from repro.launch import smoke
from repro.launch.compile_cache import CACHE_ENV, enable_compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = dict(records=4096, W=8, L=32, K=4, seed=0)


@pytest.mark.parametrize("kind", smoke.KINDS)
def test_discipline_fills_and_drains_against_reference(kind):
    st = smoke.run_discipline(kind, n_shards=1, **TINY)
    # the fullest window reached half its capacity, then drained
    assert st["peak_backlog"] >= smoke.store_cap(kind, 4096) // 2
    assert st["ops"] == st["bursts"] * TINY["K"] * TINY["L"]
    assert st["compile"]["compile_s"] > 0


def test_check_burst_catches_a_wrong_record():
    exp = {"value": np.array([[-1, 5]]), "matched": np.array([[True, True]]),
           "pos": np.array([[0, 5]])}
    got = {"matched": exp["matched"].copy(), "pos": exp["pos"].copy(),
           "dok": np.array([[False, True]]),
           "dv": np.stack([np.zeros(4, np.int32), smoke.payload_of([5], 4)[0]]
                          )[None]}
    smoke.check_burst("fifo", exp, got, 4, "ok")
    got["dv"][0, 1, 3] += 1
    with pytest.raises(smoke.SmokeFailure, match="dequeued record"):
        smoke.check_burst("fifo", exp, got, 4, "bad")


def test_four_shards_and_membership():
    out = run_multidev("""
from repro.launch import smoke
st = smoke.run_discipline("priority", n_shards=4, records=4096, W=8, L=32,
                          K=4, seed=1)
assert sorted(d for d, _ in st["placement"]) == [0, 1, 2, 3]
assert st["compile"]["all_to_all"] == 2
m = smoke.run_membership(n_from=3, records=4096, W=8, L=32, K=4, seed=1)
kinds = [(g["kind"], g["P_from"], g["P_to"]) for g in m["migrations"]]
assert kinds == [("grow", 3, 4), ("shrink", 4, 3)], kinds
assert m["migrations"][0]["moved"] > 0
print("OK")
""", n_dev=4)
    assert "OK" in out


def test_serving_tiers_and_greedy_reference():
    st = smoke.run_serving(get_config("mamba2_130m").reduced(), n_requests=8,
                           priorities=4, seed=0)
    assert st["served"] == 8 and st["tokens"] == 8 * 6


def test_compile_cache_follows_the_environment(monkeypatch, tmp_path):
    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(CACHE_ENV, str(tmp_path / "from_env"))
    assert enable_compile_cache() == str(tmp_path / "from_env")
    assert jax.config.jax_compilation_cache_dir == prev   # left to JAX
    monkeypatch.delenv(CACHE_ENV)
    try:
        path = enable_compile_cache()
        assert path == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert enable_compile_cache() == path   # fixed, not fresh
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_chip_smoke_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                          env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    assert '"ok"' not in proc.stdout
