"""The benchmark's cells cut to a size the CPU runs in a second, and a
clock that advances by itself, so that the tests leave timing out."""
import dataclasses
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

STORE = 4096           # records per chip
# a 4-tier priority structure with 8-word records: no cell of the
# benchmark runs it yet, so the tests hold the harness's tiered path and
# the priority reference to it
TIERED = "tiers4"
TIERED_CONFIG = {
    "name": TIERED, "record_words": 8, "key_word": 7,
    "structure": "ElasticDevicePriorityQueue",
    "structure_args": {"n_prios": 4, "relaxation": 0}, "tiers": 4,
    "reference": "priority", "store_records_per_chip": 1 << 20,
    "wave_ops_per_chip": 8192, "waves_per_burst": 8,
    "inputs": ["is_enq", "valid", "tier", "payload"],
    "outputs": ["tier", "pos", "matched", "dv", "dok", "ovf", "n_relaxed"],
    "op_words": {"descriptor": 3, "reply": 4}}
TIERED_MIX = {"loop": "closed", "enqueue_share": 0.5,
              "key_shares": [0.1, 0.2, 0.3, 0.4], "backlog_records": 131072,
              "prefill_key_shares": [0, 0, 0, 1]}
SIZES = dict(store_records_per_chip=STORE, wave_ops_per_chip=32,
             waves_per_burst=4)


def cells(layout, chips=1):
    """Names of the benchmark's cells on ``chips`` chips, and the tiered
    one."""
    return [w["name"] for w in layout.spec["workloads"]
            if w["chips"] == chips] + [TIERED]


def cell_named(layout, name):
    """A cell of the benchmark by name, or the tiered one, full size."""
    if name != TIERED:
        return layout.cell(name)
    from bench.harness import Cell
    e2e = [m for m in layout.spec["end_to_end"]
           if m["name"] in ("ops_per_s", "setup_s")]
    return Cell(TIERED, 1, TIERED_CONFIG, TIERED_MIX,
                layout.reference("priority"), e2e, [])


def tiny(cell):
    """The cell at tiny sizes: the backlog keeps its share of the store,
    records keep at most 8 words, an open loop offers 2000 ops/s."""
    cfg = dict(cell.config, **SIZES,
               record_words=min(cell.config["record_words"], 8))
    mix = dict(cell.mix, backlog_records=cell.mix["backlog_records"] * STORE
               // cell.config["store_records_per_chip"])
    if mix["loop"] == "open":
        mix["rate_ops_per_s"] = 2000
    return dataclasses.replace(cell, config=cfg, mix=mix)


class Clock:
    """A clock that moves ``dt`` seconds each time it is read."""

    def __init__(self, dt: float):
        self.t, self.dt = 0.0, dt

    def __call__(self) -> float:
        self.t += self.dt
        return self.t

    def sleep(self, s: float) -> None:
        self.t += s


def run_devices(script: str, n_dev: int, timeout: int = 600) -> str:
    """Run ``script`` in a fresh process that sees ``n_dev`` CPU devices."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([ROOT, os.path.join(ROOT, "src"),
                                           os.path.dirname(os.path.abspath(__file__))]))
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={n_dev} "
                        + env.get("XLA_FLAGS", "")).strip()
    proc = subprocess.run([sys.executable, "-c", script], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=timeout)
    if proc.returncode != 0:
        raise AssertionError(f"subprocess failed:\n{proc.stdout}\n"
                             f"{proc.stderr}")
    return proc.stdout
