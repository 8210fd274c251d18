"""The benchmark makes the weights itself, so that the plain reference can
make the very same ones again without taking anything from the program.
Each family makes them from the seed (``models/<family>.py``:
``make_params``); the program has to take them as they are.
"""
from __future__ import annotations


def check_layout(params, cfg) -> None:
    """The program's own parameter tree for ``cfg`` must have exactly this
    structure, shapes and dtypes: else the program would not run the model
    that the reference computes."""
    import jax

    from repro.models import build_model
    want = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    exp = jax.tree.map(lambda a: (a.shape, a.dtype), want)
    if jax.tree.structure(got) != jax.tree.structure(exp) or got != exp:
        raise RuntimeError(f"the program's parameter layout differs from the "
                           f"benchmark's: {exp} != {got}")
