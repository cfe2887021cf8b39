"""Each cell end to end on the CPU at a tiny size, through the pieces a
run drives (traffic -> ``run_waves`` -> the check), with timing left out;
and the check failing under the control and under each fault a cell can
have."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tiny import Clock, cell_named, cells, run_devices, tiny
from bench.control import ReferenceStructure
from bench.harness import CellRun, Layout, build_structure

LAYOUT = Layout()
ONE_CHIP = cells(LAYOUT)
CLOSED = [n for n in ONE_CHIP
          if cell_named(LAYOUT, n).mix["loop"] == "closed"]


def drive(cell, seed=3, structure=None, devices=None):
    run = CellRun(cell, seed, devices or jax.devices()[:cell.chips],
                  structure=structure)
    run.setup()
    clock = Clock(0.01)
    win = run.window(0.5, clock=clock, sleep=clock.sleep)
    return run, win, run.check()


@pytest.mark.parametrize("cell", ONE_CHIP)
def test_cell_runs_correct(cell):
    c = tiny(cell_named(LAYOUT, cell))
    run, win, numbers = drive(c, seed=2**31 + 3)
    assert numbers["wrong_ops"] == 0, numbers
    counts = run.window_counts()
    assert win["answered"] == win["attempted"] == counts["ops"] > 0
    assert counts["dequeued"] > 0 and counts["enqueues"] > 0
    # every op of the run was checked: set-up's bursts and the window's
    assert numbers["ops_checked"] == sum(b.valid.size
                                         for b in run.log.bursts)
    if c.mix["loop"] == "open":
        assert run.latencies.size == win["answered"]
        assert len({b.valid.shape[1] for b in run.log.bursts}) == 3


def _structure(cell, fault):
    """The program with a fault planted underneath its elastic wrapper."""
    q = build_structure(cell.config, cell.chips, jax.devices()[:cell.chips])
    inner_run, outer_run = q.inner.run_waves, q.run_waves
    if fault == "state_unchanged":
        def run_waves(state, *ops):
            keep = jax.tree.map(jnp.copy, state)
            return (keep,) + tuple(inner_run(state, *ops)[1:])
        q.inner.run_waves = run_waves
    elif fault == "half_batch":
        def run_waves(is_enq, valid, *rest):
            half = jnp.asarray(valid).shape[1] // 2
            return outer_run(is_enq, jnp.asarray(valid).at[:, half:]
                             .set(False), *rest)
        q.run_waves = run_waves
    else:
        field, at = fault.split(":")
        name = dict(zip(cell.config["outputs"], range(99)))

        def run_waves(*ops):
            out = [np.array(o) for o in outer_run(*ops)]
            dok = out[name["dok"]]
            if dok.any():
                k, i = np.argwhere(dok)[len(np.argwhere(dok)) // 2]
                if field == "dv":
                    out[name["dv"]][k, i, int(at)] += 1
                else:
                    out[name[field]][k, i] += 1
            return tuple(out)
        q.run_waves = run_waves
    return q


FAULTS = ["state_unchanged", "half_batch", "dv:0", "dv:5", "pos:0"]


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("cell", ONE_CHIP)
def test_fault_fails_the_check(cell, fault):
    c = tiny(cell_named(LAYOUT, cell))
    _, _, numbers = drive(c, structure=_structure(c, fault))
    assert numbers["wrong_ops"] > 0, numbers


@pytest.mark.parametrize("cell", ONE_CHIP)
def test_control_fails_and_plain_reference_passes(cell):
    c = tiny(cell_named(LAYOUT, cell))
    _, _, ctl = drive(c, structure=ReferenceStructure(c, control=True))
    assert ctl["wrong_ops"] > 0 and ctl["wrong_replies"] > 0, ctl
    _, _, ref = drive(c, structure=ReferenceStructure(c, control=False))
    assert ref["wrong_ops"] == 0, ref


@pytest.mark.parametrize("cell", CLOSED[:1])
def test_four_shards_and_the_exchange_left_out(cell):
    """A cell's configuration over four shards: the harness drives the
    mesh, and the check fails once the wave's all_to_all is left out."""
    out = run_devices(f"""
import dataclasses
from jax import lax
from tiny import cell_named, tiny
from bench.harness import Layout
import test_bench_cells as t

c = tiny(cell_named(Layout(), {cell!r}))
c = dataclasses.replace(c, chips=4, mix=dict(
    c.mix, backlog_records=4 * c.mix["backlog_records"]))
run, win, ok = t.drive(c)
assert ok["wrong_ops"] == 0 and win["answered"] > 0, ok
assert {{s.device.id for s in run.q.state.store_vals.addressable_shards}} \\
    == {{0, 1, 2, 3}}
for fault in ("state_unchanged", "dv:3"):
    _, _, bad = t.drive(c, structure=t._structure(c, fault))
    assert bad["wrong_ops"] > 0, (fault, bad)
lax.all_to_all = lambda x, *a, **k: x      # the exchange left out
_, _, bad = t.drive(c)
assert bad["wrong_ops"] > 0, bad
print("OK")
""", n_dev=4)
    assert "OK" in out


@pytest.mark.parametrize("cell", ONE_CHIP)
def test_result_line(cell):
    from bench.harness import peaks_for, run_cell
    c = tiny(cell_named(LAYOUT, cell))
    out = run_cell(LAYOUT, c, 5, 0.3, False, jax.devices()[:1],
                   peaks_for("TPU v5 lite"), 0.0)
    assert out["correct"] is True and out["failed"] == 0
    assert list(out)[-1] == "check"
    assert set(out["metrics"]) == {m["name"] for m in c.end_to_end}
    assert out["check"]["wrong_ops"] == {"value": 0, "limit": 0}
