"""Driving a configuration's plain reference over the steps a cell checks.

The reference module (``chipbench/configs/<reference>.py``) gives the
mathematics; this file feeds it the batches of the checked steps and hands
what comes out (losses, gradient, change of the parameters) to ``compare.py``. It runs after
the window has closed and the program's arrays are freed, one jitted step at
a time, in float32 with ``highest`` matrix precision.

``quantize`` (the control) and ``rows`` (a planted fault: part of the batch
left out) put a wrong computation in the program's place; the benchmark's own
runs pass neither.
"""

from __future__ import annotations

import importlib.util
import os
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_reference(cfg: dict):
    path = os.path.join(HERE, "configs", cfg["reference"] + ".py")
    spec = importlib.util.spec_from_file_location("chipbench_ref_" + cfg["reference"], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def seeded_weights(cfg: dict, seed: int):
    """The cell's initial weights and batch-norm state, made on the device
    in one jitted call from the seed. The program gets them installed; the
    reference starts from the same."""
    import jax

    ref = load_reference(cfg)
    return jax.jit(lambda k: ref.init(cfg, k))(jax.random.PRNGKey(seed % (2 ** 31 - 1)))


def _leaves(tree) -> List[np.ndarray]:
    import jax

    return jax.device_get(jax.tree_util.tree_leaves(tree))


def steps(cfg: dict, params0, state0, batches: Sequence[Tuple], lr: float,
          quantize_name: Optional[str] = None,
          rows=None, skip_update: bool = False) -> Dict:
    """The reference over ``batches`` (a list of (x, one-hot y), decoded to
    the model's domain): each step's loss, the first gradient and the
    parameters' change over all the steps, leaf by leaf.
    ``skip_update`` plants the fault "a step that returns its state
    unchanged"."""
    import jax
    import jax.numpy as jnp

    ref = load_reference(cfg)
    q = ref.quantizer(quantize_name)
    part = jax.jit(lambda p, s, x, y: ref.loss_and_grads(cfg, p, s, x, y, q))
    update = jax.jit(lambda p, g, o: ref.adam_update(cfg["optimizer"], p, g, o, lr))

    with jax.default_matmul_precision("highest"):
        params, state = params0, state0
        opt_state = ref.adam_init(params0)
        losses: List[float] = []
        grad = None
        for x, y in batches:
            if rows is not None:
                x, y = x[rows], y[rows]
            loss, grads, new_s = part(params, state, jnp.asarray(x), jnp.asarray(y))
            losses.append(float(loss))
            if grad is None:
                grad = _leaves(grads)
            if not skip_update:
                params, opt_state = update(params, grads, opt_state)
                state = new_s
            del grads
        change = jax.tree_util.tree_map(lambda a, b: a - b, params, params0)
        return {"losses": losses, "grad": grad, "change": _leaves(change)}


def epoch(cfg: dict, params0, state0, data, batch_fn: Callable, n_steps: int,
          lr: float, quantize_name: Optional[str] = None,
          rows=None) -> Dict:
    """The reference over one whole epoch that the program runs in one
    dispatch (the resident feed): ``batch_fn(data, i)`` is traced inside the
    scan and gives step i's decoded batch from ``data`` (device arrays,
    passed as arguments so that they are not baked into the program).
    Returns the epoch's mean loss, the per-leaf norms of Adam's first moment
    after the epoch (the gradient as the optimizer has it then), and of the
    parameters' change."""
    import jax
    import jax.numpy as jnp

    ref = load_reference(cfg)
    q = ref.quantizer(quantize_name)

    @jax.jit
    def run(params, state, data):
        def body(carry, i):
            p, s, o = carry
            x, y = batch_fn(data, i)
            p, s, o, loss, _ = ref.train_step(cfg, p, s, o, x, y, lr, q, rows)
            return (p, s, o), loss
        (p, s, o), losses = jax.lax.scan(
            body, (params, state, ref.adam_init(params)), jnp.arange(n_steps))
        change = jax.tree_util.tree_map(lambda a, b: a - b, p, params)
        return jnp.mean(losses), o["m"], change

    with jax.default_matmul_precision("highest"):
        loss, m, change = run(params0, state0, data)
        return {"losses": [float(loss)], "moment": _leaves(m),
                "change": _leaves(change)}
