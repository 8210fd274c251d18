"""Distribution context for model code.

Model functions are pure; distribution is communicated via this module-level
context set by the launcher / train-step builder before tracing.  When no
context is set (unit tests, CPU smoke runs) every layer runs its local path.

Two independent contexts exist: ``DistContext`` (training/prefill MoE
dispatch over an ambient mesh, set via ``set_context``/``use_context``)
and the serving-TP context (``tp_shard``, re-exported from
``kernels/ops``: column-splits PDQ/fp projections inside a shard_map
body; see serve/sharded.py).  The sharded serve engine deliberately runs
with ``DistContext`` unset so MoE stays replica-local.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any

from jax.sharding import PartitionSpec as P

from repro.kernels.ops import tp_ctx, tp_shard  # noqa: F401  (re-export)


@dataclasses.dataclass(frozen=True)
class DistContext:
    mesh: Any                          # jax.sharding.Mesh
    token_axes: tuple[str, ...]        # mesh axes sharding flattened tokens for MoE
    expert_axis: str                   # mesh axis experts are sharded over ('model')
    data_axes: tuple[str, ...] = ("data",)
    model_axis: str = "model"


_CTX: DistContext | None = None


def set_context(ctx: DistContext | None) -> None:
    global _CTX
    _CTX = ctx


def get_context() -> DistContext | None:
    return _CTX


@contextlib.contextmanager
def use_context(ctx: DistContext | None):
    global _CTX
    prev = _CTX
    _CTX = ctx
    try:
        yield
    finally:
        _CTX = prev


def moe_param_specs(p) -> Any:
    """shard_map in_specs for a routed-MoE param subtree."""
    return {
        "router": P(None, None),
        "we_gate": P(_CTX.expert_axis, None, None),
        "we_up": P(_CTX.expert_axis, None, None),
        "we_down": P(_CTX.expert_axis, None, None),
    }
