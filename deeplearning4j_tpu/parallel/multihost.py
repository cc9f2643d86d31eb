"""Multi-host distributed runtime: the DCN half of the communication
backend.

Role parity (SURVEY.md §5.8): the reference's cross-machine transports
are host-side — Aeron UDP parameter server and Spark RPC/shuffle, both
moving parameters as byte arrays between JVMs. The TPU-native backend
has two layers instead: **ICI** collectives inside the compiled program
(psum/all_gather inserted by GSPMD — see parallel/wrapper.py and
parallel/megatron.py), and **DCN** for cross-host process coordination
via the PJRT distributed runtime (jax.distributed): one coordinator,
N processes, each owning its local chips, with `jax.devices()` spanning
the whole job so one Mesh covers every host.

`initialize_multihost` wraps jax.distributed with env-var defaults
(the idiom TPU pod launchers use); `MultiHostLauncher` spawns local
processes for hardware-free testing — the reference's `local[N]` Spark
test trick (BaseSparkTest.java) reborn as real separate processes on a
CPU PJRT backend. Anything timed through the launcher is a CPU number.
"""
from __future__ import annotations

import inspect
import os
import pickle
import subprocess
import sys
import tempfile
import textwrap
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax


def initialize_multihost(coordinator_address: Optional[str] = None,
                         num_processes: Optional[int] = None,
                         process_id: Optional[int] = None,
                         local_device_ids: Optional[Sequence[int]] = None
                         ) -> None:
    """Join the distributed runtime. Arguments default to the standard
    env vars (JAX_COORDINATOR_ADDRESS / NUM_PROCESSES / PROCESS_ID) so
    pod launchers can configure by environment alone. On real TPU pods
    jax.distributed.initialize() autodetects everything; explicit args
    are for CPU simulation and bespoke clusters."""
    kwargs: Dict[str, Any] = {}
    coordinator_address = coordinator_address or os.environ.get(
        "JAX_COORDINATOR_ADDRESS")
    if coordinator_address is not None:
        kwargs["coordinator_address"] = coordinator_address
    num_processes = num_processes if num_processes is not None else \
        _env_int("JAX_NUM_PROCESSES")
    if num_processes is not None:
        kwargs["num_processes"] = num_processes
    process_id = process_id if process_id is not None else \
        _env_int("JAX_PROCESS_ID")
    if process_id is not None:
        kwargs["process_id"] = process_id
    if local_device_ids is not None:
        kwargs["local_device_ids"] = list(local_device_ids)
    jax.distributed.initialize(**kwargs)


def _env_int(name: str) -> Optional[int]:
    v = os.environ.get(name)
    return int(v) if v is not None else None


def process_info() -> Dict[str, int]:
    return {"process_index": jax.process_index(),
            "process_count": jax.process_count(),
            "local_device_count": jax.local_device_count(),
            "global_device_count": jax.device_count()}


class MultiHostLauncher:
    """Spawn N local python processes that each join a distributed CPU
    runtime and run `fn()` (pickled), collecting every process's return
    value. Used by tests to prove the DCN path end-to-end without
    hardware."""

    def __init__(self, num_processes: int = 2,
                 devices_per_process: int = 2, port: int = 0):
        self.num_processes = num_processes
        self.devices_per_process = devices_per_process
        if port == 0:
            import socket
            s = socket.socket()
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
            s.close()
        self.coordinator = f"127.0.0.1:{port}"

    def run(self, fn: Callable[[], Any], timeout: float = 300.0
            ) -> List[Any]:
        with tempfile.TemporaryDirectory() as td:
            fn_path = Path(td) / "fn.pkl"
            # the fn's defining module (often a test file outside any
            # package) must be importable when the subprocess unpickles
            try:
                fn_dir = str(Path(inspect.getfile(fn)).resolve().parent)
            except (TypeError, OSError):
                fn_dir = ""
            fn_path.write_bytes(pickle.dumps(fn))
            driver = textwrap.dedent(f"""
                import os, pickle, sys
                sys.path.insert(0, {fn_dir!r})
                import jax
                jax.config.update("jax_platforms", "cpu")
                jax.distributed.initialize(
                    coordinator_address="{self.coordinator}",
                    num_processes={self.num_processes},
                    process_id=int(sys.argv[1]))
                fn = pickle.loads(open({str(fn_path)!r}, "rb").read())
                result = fn()
                with open(sys.argv[2], "wb") as f:
                    pickle.dump(result, f)
            """)
            script = Path(td) / "driver.py"
            script.write_text(driver)
            procs = []
            out_paths = []
            # the launched processes are pure CPU: a chip belongs to
            # one process, and this launcher exists for hardware-free
            # tests — anything timed through it is a CPU number
            env = {k: v for k, v in os.environ.items()
                   if k not in ("PYTHONSTARTUP", "JAX_PLATFORMS",
                                "PYTHONPATH")}
            env["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                + " --xla_force_host_platform_device_count"
                                  f"={self.devices_per_process}")
            env["JAX_PLATFORMS"] = "cpu"
            pp = [p for p in os.environ.get("PYTHONPATH", "").split(
                os.pathsep) if p]
            pp.insert(0, str(Path(__file__).resolve().parents[2]))
            env["PYTHONPATH"] = os.pathsep.join(pp)
            for pid in range(self.num_processes):
                out = Path(td) / f"out_{pid}.pkl"
                out_paths.append(out)
                procs.append(subprocess.Popen(
                    [sys.executable, str(script), str(pid), str(out)],
                    env=env, stdout=subprocess.PIPE,
                    stderr=subprocess.PIPE))
            results = []
            errors = []
            for pid, p in enumerate(procs):
                try:
                    _, err = p.communicate(timeout=timeout)
                except subprocess.TimeoutExpired:
                    p.kill()
                    _, err = p.communicate()
                    errors.append(f"process {pid}: timeout\n"
                                  f"{err.decode()[-2000:]}")
                    continue
                if p.returncode != 0:
                    errors.append(f"process {pid}: rc={p.returncode}\n"
                                  f"{err.decode()[-2000:]}")
                elif out_paths[pid].exists():
                    results.append(pickle.loads(
                        out_paths[pid].read_bytes()))
            if errors:
                raise RuntimeError("multi-host launch failed:\n"
                                   + "\n".join(errors))
            return results
