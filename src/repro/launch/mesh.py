"""Production mesh definitions.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before any jax import; everything else
sees the real device count).
"""
from __future__ import annotations

import os

import jax
import numpy as np


def _auto(n: int) -> tuple:
    """Mesh axis types for jit's automatic sharding propagation, which the
    model code is written for (``jax.make_mesh`` defaults to explicit
    axes, under which a gather from a sharded table must name its output
    sharding)."""
    return (jax.sharding.AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi_pod stacks 2 pods (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    if len(jax.devices()) == n:
        return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))
    # dry-run host platform exposes 512 placeholder devices; the single-pod
    # mesh uses the first 256 of them.
    from jax.sharding import Mesh
    devs = np.asarray(jax.devices()[:n]).reshape(shape)
    return Mesh(devs, axes)


def make_host_mesh(data: int | None = None, model: int | None = None):
    """Small mesh over whatever devices exist (tests / CPU smoke runs)."""
    n = jax.device_count()
    if data is None and model is None:
        model = 1
        data = n
    elif data is None:
        data = n // model
    elif model is None:
        model = n // data
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=_auto(2))


def parse_mesh(spec: str) -> tuple[int, int]:
    """'4x2' -> (data=4, model=2)."""
    try:
        data, model = (int(p) for p in spec.lower().split("x"))
    except ValueError as e:
        raise ValueError(f"--mesh expects DxM (e.g. 4x2), got {spec!r}") from e
    if data < 1 or model < 1:
        raise ValueError(f"mesh axes must be positive, got {spec!r}")
    return data, model


def mesh_arg(argv) -> str | None:
    """The value of --mesh DxM / --mesh=DxM in argv, else None (scanned
    by hand: this runs BEFORE argparse so the device count can be sized
    first)."""
    for i, a in enumerate(argv):
        if a == "--mesh" and i + 1 < len(argv):
            return argv[i + 1]
        if a.startswith("--mesh="):
            return a.split("=", 1)[1]
    return None


def _argv_int(argv, name: str) -> int | None:
    """The integer value of --name N / --name=N in argv, else None (scanned
    by hand: this runs BEFORE argparse so the device count can be sized
    first)."""
    for i, a in enumerate(argv):
        if a == f"--{name}" and i + 1 < len(argv):
            return int(argv[i + 1])
        if a.startswith(f"--{name}="):
            return int(a.split("=", 1)[1])
    return None


def strip_forced_device_count(flags: str) -> str:
    """Drop any --xla_force_host_platform_device_count=N from an XLA_FLAGS
    string (a multi-process spawner must not leak its own forced count into
    children that need a per-process one)."""
    return " ".join(f for f in flags.split()
                    if not f.startswith("--xla_force_host_platform_device_count"))


def cpu_platform() -> bool:
    """True when JAX is pinned to the CPU backend (``JAX_PLATFORMS=cpu``).

    Read from the environment, not from jax: the callers decide before
    the first device query, and on a TPU host that query claims the chip
    for this process (a chip belongs to one process at a time)."""
    return os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


def refuse_local_processes(what: str) -> None:
    """Exit with a clear message unless JAX is pinned to the CPU: spawning
    several JAX processes on one host is a CPU-only test topology.  On an
    accelerator each process would claim every local chip; multi-process
    serving there means one process per host, each started by hand with
    --process-id/--coordinator."""
    if not cpu_platform():
        raise SystemExit(
            f"{what} starts several JAX processes on this host, which only "
            f"works on the CPU backend (JAX_PLATFORMS=cpu): on a TPU host "
            f"one process holds the chips, so run one process per host "
            f"with --process-id/--coordinator instead")


def bootstrap_mesh_env(argv) -> None:
    """Force the right number of virtual host devices for a --mesh run on
    the CPU backend: D*M for a single process, D*M // --num-processes for a
    ``jax.distributed`` child (identified by --process-id).  Off the CPU
    backend the mesh uses the real devices and nothing is forced.

    Importing this module does not initialize the jax backend, so
    XLA_FLAGS set here still takes effect - call before the first device
    query (launch/serve.py and benchmarks/bench_serve.py call it at
    module import, before anything touches jax.devices())."""
    spec = mesh_arg(argv)
    if spec is None or not cpu_platform():
        return
    data, model = parse_mesh(spec)
    want = data * model
    nprocs = _argv_int(argv, "num-processes") or 1
    if _argv_int(argv, "process-id") is not None:
        if (data * model) % nprocs:
            raise ValueError(f"mesh {data}x{model} does not split over "
                             f"{nprocs} processes")
        want = data * model // nprocs
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={want}").strip()


def pick_coordinator(addr: str | None, *, attempts: int = 5) -> str:
    """``addr`` if given, else 127.0.0.1 with a fresh OS-assigned port:
    two concurrent multi-process fleets on one host (overlapping bench
    runs, a retry racing a hung predecessor) must not rendezvous with
    each other's coordination service.  The ephemeral bind is retried
    (bounded) so transient EADDRINUSE under heavy concurrent CI does not
    kill the launcher."""
    if addr:
        return addr
    import socket
    import time as _time
    for attempt in range(attempts):
        try:
            with socket.socket() as s:
                s.bind(("127.0.0.1", 0))
                return f"127.0.0.1:{s.getsockname()[1]}"
        except OSError:
            if attempt == attempts - 1:
                raise
            _time.sleep(0.2 * (2 ** attempt))
    raise AssertionError("unreachable")


def init_distributed(coordinator: str, num_processes: int,
                     process_id: int, *, attempts: int = 3,
                     backoff: float = 1.0) -> None:
    """``jax.distributed`` bootstrap for one serve process: on the CPU
    backend collectives go through gloo (the CPU client's only
    cross-process implementation), then the coordination service connects
    this process to its peers.
    Must run before the first device query.

    The initialize is retried with exponential backoff (bounded): the
    coordination-service port can be mid-release from a previous fleet
    (TIME_WAIT) or the coordinator child can come up a beat after a
    worker - both transient, both previously fatal."""
    import jax as _jax
    if cpu_platform():
        _jax.config.update("jax_cpu_collectives_implementation", "gloo")
    for attempt in range(attempts):
        try:
            _jax.distributed.initialize(coordinator,
                                        num_processes=num_processes,
                                        process_id=process_id)
            return
        except Exception as e:
            if attempt == attempts - 1:
                raise
            import sys as _sys
            import time as _time
            try:                       # drop any half-open connection state
                _jax.distributed.shutdown()
            except Exception:
                pass
            delay = backoff * (2 ** attempt)
            print(f"init_distributed: attempt {attempt + 1}/{attempts} "
                  f"failed ({e!r}); retrying in {delay:.1f}s",
                  file=_sys.stderr, flush=True)
            _time.sleep(delay)


def make_serve_mesh(data: int, model: int):
    """('data', 'model') mesh over the first data*model devices (the
    virtual-device CPU path exposes more than the mesh needs)."""
    n = data * model
    if len(jax.devices()) < n:
        raise RuntimeError(
            f"mesh {data}x{model} needs {n} devices, found "
            f"{len(jax.devices())}; on CPU set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n}")
    from jax.sharding import Mesh
    devs = np.asarray(jax.devices()[:n]).reshape(data, model)
    return Mesh(devs, ("data", "model"))


def mesh_info(mesh) -> dict:
    return {
        "axis_names": list(mesh.axis_names),
        "shape": [int(mesh.shape[a]) for a in mesh.axis_names],
        "n_devices": int(np.prod([mesh.shape[a] for a in mesh.axis_names])),
    }
