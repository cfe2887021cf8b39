"""Compile the main path for a TPU v5e that is described, not attached.

The TPU compiler ships with jaxlib, so these tests reject at no chip time
what Mosaic or XLA:TPU would reject on the chip: the three segscan sweeps
at real widths with ``interpret=False``, and the four disciplines'
``run_waves`` bursts at deployment size (a 1 GiB store of 1 KiB records,
8192 ops per shard, 8 waves) on one chip and on the 2x2 mesh.  Nothing
runs, so results and times are out of their reach, but the compiled text
shows which loops XLA:TPU made and the wave phase each one belongs to.

The topology is described inside a module fixture: only the worker that
runs this file loads the TPU library.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis.hlo import count_op, parse_hlo, scope_table
from repro.dqueue import (DevicePriorityQueue, DeviceQueue, DeviceSeapQueue,
                          DeviceStack)
from repro.dqueue.wave_engine import WAVE_PHASES
from repro.kernels.segscan.kernel import (queue_scan_kernel,
                                          stack_scan_kernel,
                                          tiered_queue_scan_kernel)
from repro.launch import smoke

RECORDS, W, L, K = 1 << 20, 256, 8192, 8


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without the chip
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield topo
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Trace the wave path's pallas sweeps compiled, as on a TPU backend
    (the CPU backend would pick interpret mode and the jnp scans)."""
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")


def _mesh(topo, n):
    return jax.sharding.Mesh(np.array(topo.devices[:n]), ("data",))


@pytest.mark.parametrize("sweep", ["queue", "stack", "tiered"])
def test_segscan_sweep_compiles(topo, sweep):
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    b = jax.ShapeDtypeStruct((L,), jnp.bool_, sharding=one)
    i = jax.ShapeDtypeStruct((), jnp.int32, sharding=one)
    if sweep == "tiered":
        t = jax.ShapeDtypeStruct((L,), jnp.int32, sharding=one)
        v = jax.ShapeDtypeStruct((smoke.N_BUCKETS,), jnp.int32, sharding=one)
        fn, args = (lambda *a: tiered_queue_scan_kernel(
            *a, smoke.N_BUCKETS, interpret=False)), (t, b, v, v)
    else:
        kern = queue_scan_kernel if sweep == "queue" else stack_scan_kernel
        fn, args = (lambda *a: kern(*a, interpret=False)), (b, b, i, i)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _wave_program(topo, kind, n_dev):
    """Compile ``kind``'s run_waves burst at deployment size on the first
    ``n_dev`` described chips; returns the compiled executable."""
    mesh = _mesh(topo, n_dev)
    kw = dict(cap=smoke.store_cap(kind, RECORDS), payload_width=W,
              ops_per_shard=L)
    q = {"fifo": lambda: DeviceQueue(mesh, "data", **kw),
         "lifo": lambda: DeviceStack(mesh, "data",
                                     slot_depth=smoke.SLOT_DEPTH, **kw),
         "priority": lambda: DevicePriorityQueue(
             mesh, "data", n_prios=smoke.N_TIERS, **kw),
         "seap": lambda: DeviceSeapQueue(mesh, "data",
                                         n_buckets=smoke.N_BUCKETS, **kw),
         }[kind]()
    state = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                          sharding=NamedSharding(mesh, s)),
        jax.eval_shape(q.init_state), q.engine.disc.state_specs)
    op = NamedSharding(mesh, P(None, "data"))
    n = n_dev * L
    ops = [jax.ShapeDtypeStruct((K, n), jnp.bool_, sharding=op)] * 2
    if kind in ("priority", "seap"):
        ops.append(jax.ShapeDtypeStruct((K, n), jnp.int32, sharding=op))
    ops.append(jax.ShapeDtypeStruct((K, n, W), jnp.int32, sharding=op))
    return q, q._run_waves.lower(state, *ops).compile()


@pytest.fixture(scope="module")
def fifo_burst(topo):
    """The FIFO burst compiled once per mesh size for every test that
    reads it: n_dev -> compiled executable."""
    done = {}

    def get(n_dev):
        if n_dev not in done:
            done[n_dev] = _wave_program(topo, "fifo", n_dev)[1]
        return done[n_dev]
    return get


def test_fifo_burst_compiles_on_one_chip(fifo_burst):
    c = fifo_burst(1)
    mem = c.memory_analysis()
    assert mem.argument_size_in_bytes >= RECORDS * W * 4   # the 1 GiB store
    assert mem.alias_size_in_bytes >= RECORDS * W * 4      # donated in place


@pytest.mark.parametrize("kind", ["lifo", "priority", "seap"])
def test_fused_dispatch_burst_compiles_kernel(topo, compiled_kernels, kind):
    """The LIFO, priority and Seap waves carry the compiled segscan sweep
    (a kernel left in interpret mode has no tpu_custom_call)."""
    q, c = _wave_program(topo, kind, 1)
    assert q.engine.disc.fused_dispatch
    assert "tpu_custom_call" in c.as_text()


def test_fifo_burst_on_2x2_mesh_has_two_all_to_alls(fifo_burst):
    assert count_op(fifo_burst(4).as_text(), "all-to-all") == 2


@pytest.mark.parametrize("n_dev", [1, 4])
def test_fifo_burst_scan_is_its_only_loop(fifo_burst, n_dev):
    """No loop in phase ``reply``: each op's reply is selected, not
    gathered one row slice at a time."""
    text = fifo_burst(n_dev).as_text()
    phases = scope_table(text, WAVE_PHASES)
    loops = [op.var.lstrip("%") for op in parse_hlo(text).ops
             if op.opcode == "while"]
    in_reply = [w for w in loops if phases.get(w) == "reply"]
    assert in_reply == [], in_reply
    assert len(loops) == 1, loops          # the burst's lax.scan


@pytest.mark.parametrize("width", [2048, 4096, 8192])
def test_scheduler_burst_keeps_its_dispatch_free_of_loops(topo,
                                                          compiled_kernels,
                                                          width):
    """The 4-tier burst of 32-B records over a 2^23-record store (the
    request scheduler's deployment), at each ladder width of an 8192-op
    wave: the segscan sweep's kernels sit in phase ``tier_scan``, and no
    loop sits in the dispatch, its tier scan or its batch-DeleteMin."""
    mesh = _mesh(topo, 1)
    q = DevicePriorityQueue(mesh, "data", n_prios=4, cap=1 << 21,
                            payload_width=8, ops_per_shard=L)
    state = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                          sharding=NamedSharding(mesh, s)),
        jax.eval_shape(q.init_state), q.engine.disc.state_specs)
    op = NamedSharding(mesh, P(None, "data"))
    ops = [jax.ShapeDtypeStruct((K, width), dt, sharding=op)
           for dt in (jnp.bool_, jnp.bool_, jnp.int32)]
    ops.append(jax.ShapeDtypeStruct((K, width, 8), jnp.int32, sharding=op))
    text = q._run_waves.lower(state, *ops).compile().as_text()
    phases = scope_table(text, WAVE_PHASES)
    kernels = [line.split("=")[0].strip().lstrip("%")
               for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == 2
    assert {phases.get(k) for k in kernels} == {"tier_scan"}
    ops = parse_hlo(text).ops
    loops = [op.var.lstrip("%") for op in ops if op.opcode == "while"]
    in_dispatch = [w for w in loops
                   if phases.get(w) in ("dispatch", "tier_scan", "deletemin")]
    assert in_dispatch == [], in_dispatch
    # each op's tier row is selected, not gathered one scalar at a time
    gathers = [op.var.lstrip("%") for op in ops if op.opcode == "gather"
               and phases.get(op.var.lstrip("%")) == "tier_scan"]
    assert gathers == [], gathers
