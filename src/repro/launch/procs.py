"""Local multi-process ``jax.distributed`` spawn recipe (demo/CI).

Three surfaces spawn cooperating worker processes on one machine — the
``launch/serve.py --hosts N`` driver, the ``benchmarks/service_bench.py``
multi-host scenario and ``tests/multihost/run_multiprocess.py`` — and they
must agree on the fiddly parts: a free coordinator port, a worker
environment pinned to the CPU backend with the forced-host-device-count
flag scrubbed (each worker owns exactly one local device), and supervision
that cannot leak children on a hang.  This module is the single owner of
that recipe.

The recipe is a CPU demo: on a machine whose JAX backend is an accelerator
:func:`run_workers` refuses, rather than serve from the CPU while this
process holds the chip.  There, one process drives every local chip (the
``sharded`` backend over ``launch.mesh.make_index_mesh``).
"""
from __future__ import annotations

import os
import socket
import subprocess
import time

import jax

__all__ = ["free_coordinator", "require_cpu_backend", "run_workers",
           "worker_env"]


def require_cpu_backend(what: str) -> None:
    """Raise ``RuntimeError`` unless this process's JAX backend is the CPU.

    Spawned workers are pinned to the CPU (:func:`worker_env`); on an
    accelerator host they would quietly serve from the CPU while this
    process holds the chip."""
    backend = jax.default_backend()
    if backend != "cpu":
        raise RuntimeError(
            f"{what} spawns CPU worker processes (a multi-process CPU "
            f"demo), but this machine's JAX backend is {backend!r}: one "
            f"process drives every local chip — serve the 'sharded' "
            f"backend over repro.launch.mesh.make_index_mesh instead")


def free_coordinator(host: str = "127.0.0.1") -> str:
    """``host:port`` with a currently free TCP port for the
    ``jax.distributed`` coordinator.  (Best-effort: the port is released
    before the workers bind it — the standard local-spawn race, fine for
    demo/CI single-machine use.)"""
    with socket.socket() as s:
        s.bind((host, 0))
        return f"{host}:{s.getsockname()[1]}"


def worker_env(base: dict | None = None) -> dict:
    """Worker-process environment: CPU backend, no forced host device
    count (a worker's device count is its real local one)."""
    env = dict(os.environ if base is None else base)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    return env


def run_workers(commands: list[list[str]], *, timeout: float = 600.0,
                capture: bool = False) -> tuple[list[int], list[str]]:
    """Spawn one process per command, wait for all under one deadline.

    Returns ``(exit_codes, stdouts)`` (stdouts empty unless ``capture``).
    On deadline every straggler is killed and reported as exit code 124 —
    a hung collective never wedges the caller.  Refuses (``RuntimeError``,
    nothing spawned) on an accelerator host: :func:`require_cpu_backend`.
    """
    require_cpu_backend("spawning local jax.distributed workers")
    env = worker_env()
    procs = [subprocess.Popen(cmd, env=env,
                              stdout=subprocess.PIPE if capture else None,
                              text=capture)
             for cmd in commands]
    deadline = time.monotonic() + timeout
    codes, outs = [], []
    for p in procs:
        left = max(deadline - time.monotonic(), 0.0)
        try:
            out, _ = p.communicate(timeout=left)
        except subprocess.TimeoutExpired:
            p.kill()
            out, _ = p.communicate()
            codes.append(124)
            outs.append(out or "")
            continue
        codes.append(p.returncode)
        outs.append(out or "")
    return codes, outs
