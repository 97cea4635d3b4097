"""Where a Pallas kernel runs: the one dispatch rule of the package.

On a TPU every kernel is compiled by Mosaic and no call runs it in
interpret mode.  Off the TPU the same kernel body runs in Pallas interpret
mode, which is what the CPU tests pin against the jnp oracles.  A caller
that leaves ``use_pallas=None`` gets the kernel on the TPU and the XLA
formulation elsewhere (interpret mode only measures the interpreter); an
explicit ``True``/``False`` is honored on every backend.
"""
from __future__ import annotations

import jax


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def interpret() -> bool:
    """``interpret=`` for every ``pallas_call``: compiled on the TPU only."""
    return not on_tpu()


def resolve_use_pallas(use_pallas: bool | None) -> bool:
    """None -> the Pallas kernel on the TPU, the XLA formulation elsewhere."""
    return on_tpu() if use_pallas is None else bool(use_pallas)
