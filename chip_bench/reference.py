"""The plain reference's run and the comparison that decides ``correct``.

Each architecture (``chip_bench/archs/<arch>.py``) writes its training
step in straightforward single-device jax from the configuration file
alone, with plain SGD on every parameter (``reference_step``); it
imports nothing of the system under test and is given nothing it made.
``run_reference`` drives that step over the checked batches.

The comparison follows the first three steps of a run.  Its numbers:

- ``loss_gap``: the worst of the three steps' |loss - reference loss|
  / |reference loss|.
- ``grad_gap``: the first gradient as the optimizer got it, worked out
  from the state after one step, ``(p0 - p1) / lr``; per leaf the gap
  between the program's norm and the reference's, over the larger of
  that leaf's reference norm and the median leaf's.  The worst leaf.
- ``change_gap``: the same, of the parameters' change after three steps,
  ``p3 - p0``.

Norms and not elementwise differences, because summation order alone
moves single entries a long way: at a max-pool near-tie a reordered sum
can flip which of two (nearly) equal pre-pool values wins, which routes
one pixel's gradient term to a neighbour and moves the dW entries of
that kernel by about one term of a sum over B*H*W pixels (on a TPU v5e:
one conv2 kernel of 1500 moved by 1.36e-3 of the largest update,
the others below 1e-4).  Such a flip changes a leaf's norm by far less
than it changes its largest entry.

Leaves whose reference gradient is below a thousandth of the median
leaf's move by round-off alone and are left out of ``grad_gap`` and
``change_gap`` (a rule on the reference's gradient, not on names).
"""
from __future__ import annotations

import numpy as np

NEGLIGIBLE = 1e-3  # a leaf whose reference gradient norm is below this
#                    share of the median leaf's is left out


def precision_of(name: str):
    """``lax.Precision`` of a configuration's ``matmul_precision``."""
    from jax import lax

    return {"highest": lax.Precision.HIGHEST, "high": lax.Precision.HIGH}[name]


def run_reference(step, params, images, labels, steps: int = 3):
    """States and losses of ``step(params, images, labels) -> (params,
    loss)`` (an architecture's ``reference_step``) over ``steps`` steps
    from ``params`` on batches ``images[i]``/``labels[i]``: ``(states,
    losses)`` with ``states[i]`` the host copy after step ``i + 1``."""
    import jax

    states, losses = [], []
    p = params
    for i in range(steps):
        p, loss = step(p, images[i], labels[i])
        states.append(to_host(p))
        losses.append(float(loss))
    jax.block_until_ready(p)
    return states, losses


def to_host(tree, prefix: str = "", out: dict = None) -> dict:
    """``{"conv1.kernel": float64 array, ...}`` from a parameter tree of
    nested dicts of any depth, its keys joined by dots."""
    out = {} if out is None else out
    for name, value in tree.items():
        key = f"{prefix}{name}"
        if isinstance(value, dict):
            to_host(value, f"{key}.", out)
        else:
            out[key] = np.asarray(value, np.float64)
    return out


def _norms(tree: dict) -> dict:
    return {k: float(np.linalg.norm(v)) for k, v in tree.items()}


def leaf_gaps(prog: dict, ref: dict, keep) -> dict:
    """Per kept leaf, the gap between the norms over the larger of the
    leaf's and the median leaf's reference norm."""
    pn, rn = _norms(prog), _norms(ref)
    floor = float(np.median(list(rn.values())))
    return {k: abs(pn[k] - rn[k]) / max(rn[k], floor, 1e-30) for k in keep}


def _worst(gaps: dict) -> float:
    values = list(gaps.values())
    return max(values) if np.all(np.isfinite(values)) else float("inf")


def compare(p0: dict, lr: float, prog_states, prog_losses, ref_states, ref_losses,
            detail: dict = None) -> dict:
    """The numbers compared (see the module docstring).  ``p0`` and the
    states are host trees from ``to_host``; states hold the parameters
    after steps 1..3.  ``detail``, if given, receives every leaf's gaps."""
    loss_gaps = [abs(a - b) / abs(b) for a, b in zip(prog_losses, ref_losses)]
    loss_gap = max(loss_gaps) if np.all(np.isfinite(loss_gaps)) else float("inf")
    g_prog = {k: (p0[k] - prog_states[0][k]) / lr for k in p0}
    g_ref = {k: (p0[k] - ref_states[0][k]) / lr for k in p0}
    ref_norms = _norms(g_ref)
    median = float(np.median(list(ref_norms.values())))
    keep = [k for k in p0 if ref_norms[k] >= NEGLIGIBLE * median]
    d_prog = {k: prog_states[-1][k] - p0[k] for k in p0}
    d_ref = {k: ref_states[-1][k] - p0[k] for k in p0}
    gaps = {"grad_gap": leaf_gaps(g_prog, g_ref, keep),
            "change_gap": leaf_gaps(d_prog, d_ref, keep)}
    if detail is not None:
        detail.update(gaps, losses=list(zip(prog_losses, ref_losses)))
    return {"loss_gap": loss_gap, **{k: _worst(v) for k, v in gaps.items()}}
