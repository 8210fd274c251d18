"""The device check: a TPU whose ``device_kind`` is in the peaks table, with
as many chips as the cell asks for.  Anything else is an error; the
benchmark never falls back to the CPU."""
from __future__ import annotations

from pathlib import Path


class NoChip(RuntimeError):
    pass


def require_chip(chips: int, root: Path) -> dict:
    import jax

    from chipbench import bench
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {dev.platform!r}): the "
                     "benchmark runs on the chip only")
    peaks = bench.load_peaks(root)
    if dev.device_kind not in peaks:
        raise NoChip(f"device_kind {dev.device_kind!r} is not in "
                     f"roofline/peaks.json ({sorted(peaks)})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devices)}")
    print(f"[server] device_kind: {dev.device_kind}  devices: {len(devices)}  "
          f"jax {jax.__version__}", flush=True)
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}
