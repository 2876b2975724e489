"""Step timing by the slope method.

The reference times loops with barrier-synchronized `tic()/toc()`
(`/root/reference/src/tools.jl:228-234`).  A timed region that ends in one
sync also holds the constant cost of dispatching the batch and reading the
result back, which is not small next to a sub-millisecond step.

:func:`time_steps` instead measures seconds/step by the **slope method**:
time a batch of N1 dispatches and a batch of N2 dispatches, each ended by the
same scalar device->host read; the constant dispatch/read cost cancels in
`(T2 - T1) / (N2 - N1)`.
"""

from __future__ import annotations

import time
from typing import Callable, Tuple

__all__ = ["time_steps"]


def _sync_read(state) -> None:
    """Force completion of everything enqueued: read one scalar back."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    leaf = jax.tree.leaves(state)[0]
    if hasattr(leaf, "ndim") and leaf.ndim > 0:
        leaf = jnp.ravel(leaf)[0]
    np.asarray(jax.device_get(leaf))


def time_steps(step: Callable, state: Tuple, *, n1: int = 10, n2: int = 50,
               warmup: int = 3) -> Tuple[Tuple, float]:
    """Seconds per call of `state = step(*state)`, slope-measured.

    `step` takes the state tuple's elements and returns the new state (tuple,
    or a single array for 1-element states).  Returns `(state, sec_per_call)`.
    """
    if n2 <= n1:
        raise ValueError(f"need n2 > n1, got n1={n1} n2={n2}")

    def advance(n: int) -> float:
        nonlocal state
        t0 = time.monotonic()
        for _ in range(n):
            out = step(*state)
            state = out if isinstance(out, tuple) else (out,)
        _sync_read(state)
        return time.monotonic() - t0

    state = tuple(state) if isinstance(state, tuple) else (state,)
    advance(warmup)
    t1 = advance(n1)
    t2 = advance(n2)
    # The number of executed calls is deterministic — exactly
    # `warmup + n1 + n2` — so physics driven through this timer is
    # reproducible run to run.
    if t2 > t1:
        return state, (t2 - t1) / (n2 - n1)
    # Noise swamped the slope (t2 <= t1, e.g. a lingering recompile in the
    # first batch): fall back to the batch-2 average — an overestimate (it
    # includes the constant readback latency) but never zero/negative.
    return state, t2 / n2
