"""Shared dispatch-tier selection for the Pallas kernel layer.

Every kernel in `repro.kernels` ships three equivalent implementations:

  * ``pallas``    — the compiled Mosaic kernel (TPU);
  * ``interpret`` — the same kernel body run by the Pallas interpreter
                    (validates kernel logic on CPU CI);
  * ``reference`` — a pure-jnp formulation, bit-identical by contract
                    (fastest off-TPU for shapes the interpreter crawls
                    on).

Before this module each kernel's ``ops.py`` carried its own copy of the
same three decisions; they are centralized here so `tdc` / `intgemm` /
`gru` / `fex_fused` resolve identically:

  1. `resolve_dispatch` — map ("auto" | explicit tier, legacy
     ``interpret=`` flag) to a concrete tier for this jax backend.
  2. `force_dispatch` — a thread-local override consulted before
     everything else: it pins every kernel entry point traced inside
     it to one tier (the v5e compile tests lower the Pallas tier from
     a CPU host; `chip_smoke.py` replays on the reference tier).
"""

from __future__ import annotations

import contextlib
import threading
from typing import Optional

import jax

__all__ = [
    "DISPATCH_TIERS",
    "dispatch_override",
    "force_dispatch",
    "resolve_dispatch",
]

DISPATCH_TIERS = ("pallas", "interpret", "reference")

_local = threading.local()


def dispatch_override() -> Optional[str]:
    """The tier forced by an enclosing `force_dispatch`, or None."""
    return getattr(_local, "tier", None)


@contextlib.contextmanager
def force_dispatch(tier: str):
    """Force every kernel dispatch in this thread to ``tier``.

    Overrides BOTH the ``dispatch=`` argument and the legacy
    ``interpret=`` flag of every kernel entry point resolved inside the
    context — this is the no-nested-`pallas_call` escape hatch for
    kernel bodies that trace other kernels' entry points (see module
    docstring). Thread-local and re-entrant.
    """
    if tier not in DISPATCH_TIERS:
        raise ValueError(
            f"unknown dispatch tier {tier!r}; expected one of "
            f"{DISPATCH_TIERS}"
        )
    prev = dispatch_override()
    _local.tier = tier
    try:
        yield
    finally:
        _local.tier = prev


def resolve_dispatch(
    dispatch: str = "auto",
    interpret: Optional[bool] = None,
    *,
    off_tpu: str = "reference",
    on_tpu: str = "pallas",
    has_reference: bool = True,
) -> str:
    """Resolve ('auto' | tier, legacy flag) to a concrete dispatch tier.

    Precedence: an enclosing `force_dispatch` wins over everything;
    then the legacy ``interpret=`` flag (True -> "interpret", False ->
    "pallas"); then an explicit ``dispatch=`` tier; then "auto" picks
    ``on_tpu`` on TPU ("pallas" unless Mosaic refuses the kernel there)
    and ``off_tpu`` elsewhere (each kernel states its own off-TPU
    default: "reference" where the jnp formulation is the
    fast path, "interpret" where the interpreter is cheap enough to
    keep CI exercising the kernel body — `tdc` flips between the two
    on batch size).

    Kernels without a standalone reference tier (``has_reference=
    False``: `gru`, `fex_fused`) degrade a forced/explicit "reference"
    to "interpret" — the interpreter is their bit-identical non-Mosaic
    evaluation of the same body.
    """
    forced = dispatch_override()
    if forced is not None:
        return forced if has_reference or forced != "reference" else "interpret"
    if interpret is not None:  # legacy flag wins when given explicitly
        return "interpret" if interpret else "pallas"
    if dispatch != "auto":
        if dispatch not in DISPATCH_TIERS:
            raise ValueError(
                f"unknown dispatch {dispatch!r}; "
                "expected 'auto', 'pallas', 'interpret' or 'reference'"
            )
        if dispatch == "reference" and not has_reference:
            return "interpret"
        return dispatch
    if jax.default_backend() == "tpu":
        return on_tpu
    return off_tpu

