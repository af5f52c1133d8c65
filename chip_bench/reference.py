"""The plain reference: the paper's CNN training step in straightforward
single-device jax, written from the configuration file alone.  It
imports nothing of the system under test and is given nothing it made.

    conv(5x5, C1) + b -> ReLU -> LRN -> maxpool/2 ->
    conv(5x5, C2) + b -> ReLU -> LRN -> maxpool/2 -> fc -> softmax loss

then plain SGD on every parameter.  Every convolution and matrix product
runs at the precision the configuration states ("highest": fp32), so
the program and this step differ only in summation order.

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

import functools

import numpy as np

NEGLIGIBLE = 1e-3  # a leaf whose reference gradient norm is below this
#                    share of the median leaf's is left out


def _precision(name: str):
    from jax import lax

    return {"highest": lax.Precision.HIGHEST, "high": lax.Precision.HIGH}[name]


def plain_conv(x, w, precision):
    """NHWC x HWIO, stride 1, SAME."""
    from jax import lax

    return lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=precision,
    )


def lrn(x, size: int, alpha: float, beta: float, k: float):
    """Cross-channel local response normalisation:
    x / (k + alpha * sum of x^2 over the ``size`` channels around)^beta."""
    import jax.numpy as jnp

    half = size // 2
    sq = jnp.pad(jnp.square(x), [(0, 0)] * 3 + [(half, size - 1 - half)])
    c = x.shape[-1]
    window = sum(sq[..., i:i + c] for i in range(size))
    return x / jnp.power(k + alpha * window, beta)


def maxpool(x, s: int):
    """``s`` x ``s`` windows, stride ``s``, no padding."""
    import jax.numpy as jnp
    from jax import lax

    return lax.reduce_window(x, -jnp.inf, lax.max, (1, s, s, 1), (1, s, s, 1), "VALID")


def plain_dot(x, w, precision):
    import jax.numpy as jnp

    return jnp.dot(x, w, precision=precision)


def loss_fn(params, images, labels, lrn_args, pool: int, precision: str = "highest",
            conv=plain_conv, dot=plain_dot):
    """Mean softmax cross-entropy of the CNN; ``lrn_args`` is (size,
    alpha, beta, k); ``conv(x, w, precision)`` and ``dot(x, w,
    precision)`` compute the convolutions and the fc product (replaced
    only by the control and the planted faults)."""
    import jax
    import jax.numpy as jnp

    prec = _precision(precision)
    x = images
    for layer in ("conv1", "conv2"):
        x = conv(x, params[layer]["kernel"], prec) + params[layer]["bias"]
        x = maxpool(lrn(jax.nn.relu(x), *lrn_args), pool)
    x = x.reshape(x.shape[0], -1)
    logits = dot(x, params["fc"]["kernel"], prec) + params["fc"]["bias"]
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))


@functools.lru_cache(maxsize=16)
def _jitted_step(lrn_args, pool: int, lr: float, precision: str, conv, dot):
    import jax

    def step(params, images, labels):
        loss, grads = jax.value_and_grad(loss_fn)(
            params, images, labels, lrn_args, pool, precision, conv, dot)
        return jax.tree.map(lambda p, g: p - lr * g, params, grads), loss

    return jax.jit(step)


def reference_step(cfg: dict, lr: float, precision: str = "highest", conv=plain_conv,
                   dot=plain_dot):
    """``step(params, images, labels) -> (new_params, loss)``, jitted."""
    n = cfg["lrn"]
    lrn_args = (int(n["size"]), float(n["alpha"]), float(n["beta"]), float(n["k"]))
    return _jitted_step(lrn_args, int(cfg["pool_stride"]), float(lr), precision, conv, dot)


def run_reference(cfg, lr, params, images, labels, steps: int = 3,
                  precision: str = "highest", conv=plain_conv):
    """The reference's states and losses over ``steps`` steps from
    ``params`` on batches ``images[i]``/``labels[i]``: ``(states,
    losses)`` with ``states[i]`` the host copy after step ``i + 1``."""
    import jax

    step = reference_step(cfg, lr, precision, conv)
    states, losses = [], []
    p = params
    for i in range(steps):
        p, loss = step(p, images[i], labels[i])
        states.append(to_host(p))
        losses.append(float(loss))
    jax.block_until_ready(p)
    return states, losses


def to_host(tree) -> dict:
    """``{"conv1.kernel": float64 array, ...}`` from a parameter tree."""
    out = {}
    for layer, leaves in tree.items():
        for name, a in leaves.items():
            out[f"{layer}.{name}"] = np.asarray(a, np.float64)
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
