"""``adamw.py``'s plain AdamW, with the parameters committed to the device
before the first step: the same arithmetic, the same state, the same
arguments for the trainer; ONE compile of the family's loss in a run where
``adamw.py`` gives two.

Why. ``common.train_steps`` hands ``grad_fn`` the seeded parameters as
``jnp.asarray`` made them, uncommitted, at the first step, and ``update``'s
outputs from the second on, which are committed to the device because
``adamw.init`` puts the moments in host memory with ``device_put``. To
``jax.jit`` those are two signatures, to the lowering two module texts
(the second carries a sharding on every parameter), to the compile cache
two keys: the loss and the update compile twice in a run, and a cell that
checks two steps pays both compiles for them. For ``joyai-llm-flash-ep32-
bf16`` that is 72 s of a run that has 360 s (PERF.md section 7.19(a), my
chip runs, PR 36). The repair is one line of ``common.train_steps``
(``params = jax.device_put(params, jax.devices()[0])`` before the loop),
which is not a line a PR that adds a cell may edit.

What this module does instead: ``init`` is the one call that sees the
driver's ``params`` before the first step, and it commits them IN PLACE
(the driver's dict, leaf by leaf; no copy is made: ``device_put`` of an
array to the device it is on only marks it). The driver's ``start`` is the
same dict. Once ``train_steps`` commits its parameters itself, a
configuration names ``adamw`` again and this file goes.
"""

from __future__ import annotations

import jax

from . import adamw
from .adamw import argv, program_trace1, trace1, update  # noqa: F401


def init(params):
    """``adamw.init`` of ``params``, which are committed to the first
    device on the way (in place: see the module docstring), and the step
    count with them: an uncommitted count at the first step would be a
    second signature of ``update``."""
    device = jax.devices()[0]
    for name in params:
        params[name] = jax.device_put(params[name], device)
    state = adamw.init(params)
    return dict(state, count=jax.device_put(state["count"], device))
