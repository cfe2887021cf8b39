"""Localhost multi-process launcher for DistributedRuntime: a CPU test
harness, not a chip path.

Every child it starts is forced onto the CPU (``JAX_PLATFORMS=cpu`` and a
forced device count), so it never touches an accelerator; ``chip_smoke.py``
at the repo root is the path that runs on the chip.

Spawns ``n_procs`` python subprocesses, each forced to
``devs_per_proc`` CPU devices, wired to one coordinator port via the
``REPRO_RT_*`` environment (which ``DistributedRuntime.from_env``
consumes).  This is how the multiprocess CI leg, the distributed
differential test, and the BENCH_PR10 wire measurement all run 2
processes x 4 CPU devices on one machine.

The child is an ordinary python program: a script path, or inline code
via ``code=``.  Its first jax-touching line should be
``DistributedRuntime.from_env()`` (device-count forcing only works
before jax initializes, which is why it must ride the child's
environment rather than a jax call).
"""
from __future__ import annotations

import os
import socket
import subprocess
import sys
from typing import Dict, List, NamedTuple, Optional, Sequence

from .distributed import ENV_COORD, ENV_NPROCS, ENV_PID


def find_free_port(host: str = "127.0.0.1") -> int:
    """An OS-assigned free TCP port (released immediately; the race
    window is acceptable for localhost test launches)."""
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind((host, 0))
        return s.getsockname()[1]


class ProcResult(NamedTuple):
    """One child's outcome."""
    process_id: int
    returncode: int
    stdout: str
    stderr: str


def _child_env(pid: int, n_procs: int, devs_per_proc: int, coord: str,
               extra_env: Optional[Dict[str, str]]) -> Dict[str, str]:
    env = dict(os.environ)
    env.update({
        ENV_COORD: coord,
        ENV_NPROCS: str(n_procs),
        ENV_PID: str(pid),
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": f"--xla_force_host_platform_device_count="
                     f"{devs_per_proc}",
    })
    if extra_env:
        env.update(extra_env)
    return env


def launch_localhost(script: Optional[str] = None, *,
                     code: Optional[str] = None,
                     args: Sequence[str] = (),
                     n_procs: int = 2, devs_per_proc: int = 4,
                     timeout: float = 600.0,
                     extra_env: Optional[Dict[str, str]] = None,
                     check: bool = True) -> List[ProcResult]:
    """Run ``n_procs`` copies of a python program as one jax world.

    Args:
      script: path to a python file to run (mutually exclusive with
        ``code``); ``code`` runs inline via ``python -c``.
      args: extra argv passed to every child.
      n_procs / devs_per_proc: world shape (total shards =
        ``n_procs * devs_per_proc``).
      timeout: per-child wait in seconds (the world hangs if any child
        dies before ``initialize`` — the timeout is the backstop).
      extra_env: additional environment for every child.
      check: raise ``RuntimeError`` (with the failing child's stderr)
        on any nonzero exit.

    Returns:
      One :class:`ProcResult` per process, in process-id order.
    """
    if (script is None) == (code is None):
        raise ValueError("pass exactly one of script= or code=")
    coord = f"127.0.0.1:{find_free_port()}"
    cmd = [sys.executable]
    cmd += ["-c", code] if code is not None else [script]
    cmd += list(args)
    procs = []
    for pid in range(n_procs):
        procs.append(subprocess.Popen(
            cmd, env=_child_env(pid, n_procs, devs_per_proc, coord,
                                extra_env),
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
    results: List[ProcResult] = []
    try:
        for pid, p in enumerate(procs):
            out, err = p.communicate(timeout=timeout)
            results.append(ProcResult(pid, p.returncode, out, err))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.communicate()
    if check:
        for r in results:
            if r.returncode != 0:
                raise RuntimeError(
                    f"distributed child {r.process_id}/{n_procs} exited "
                    f"{r.returncode}\n--- stdout ---\n{r.stdout}\n"
                    f"--- stderr ---\n{r.stderr}")
    return results
