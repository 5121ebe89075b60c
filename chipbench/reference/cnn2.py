"""Plain float32 reference of the paper's two-layer CNN clients (MNIST and
FEMNIST, paper §IV-A2), written from the paper's description alone.

conv kxk (c1) -> relu -> maxpool 2 -> conv kxk (c2) -> relu -> maxpool 2
-> flatten -> dense (fc_hidden) -> relu -> dense (n_classes), NHWC inputs,
HWIO kernels, softmax cross-entropy averaged over the batch. Initial
weights: for each parameter in the order above (kernel, then bias), one
``jax.random.split`` of ``PRNGKey(seed)``; kernels are truncated normals
on [-2, 2] scaled by 1/sqrt(fan_in), biases zero.

Every convolution and matrix product takes an explicit precision, so the
reference does not depend on the process's default matmul precision.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _out_hw(arch: dict) -> int:
    """Spatial size after the two conv+pool blocks."""
    hw, k = arch["input_hw"], arch["kernel"]
    for _ in range(2):
        if arch["padding"] == "VALID":
            hw = hw - k + 1
        hw //= 2
    return hw


def param_shapes(arch: dict) -> list[tuple[str, tuple[int, ...], bool]]:
    """(name, shape, is_kernel) in creation order."""
    k, cin = arch["kernel"], arch["in_channels"]
    c1, c2 = arch["conv_channels"]
    flat = _out_hw(arch) ** 2 * c2
    h, n = arch["fc_hidden"], arch["n_classes"]
    return [("c1_w", (k, k, cin, c1), True), ("c1_b", (c1,), False),
            ("c2_w", (k, k, c1, c2), True), ("c2_b", (c2,), False),
            ("fc1_w", (flat, h), True), ("fc1_b", (h,), False),
            ("fc2_w", (h, n), True), ("fc2_b", (n,), False)]


def n_params(arch: dict) -> int:
    return sum(math.prod(s) for _, s, _ in param_shapes(arch))


def init(arch: dict, seed: int) -> dict:
    key = jax.random.PRNGKey(seed)
    params = {}
    for name, shape, is_kernel in param_shapes(arch):
        key, sub = jax.random.split(key)
        if is_kernel:
            std = 1.0 / math.sqrt(math.prod(shape[:-1]))
            params[name] = jax.random.truncated_normal(
                sub, -2.0, 2.0, shape, jnp.float32) * std
        else:
            params[name] = jnp.zeros(shape, jnp.float32)
    return params


def _conv(x, w, b, padding, precision, dtype):
    y = jax.lax.conv_general_dilated(
        x.astype(dtype), w.astype(dtype), window_strides=(1, 1),
        padding=padding, dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=precision)
    return y.astype(jnp.float32) + b


def _pool(x):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                 (1, 2, 2, 1), (1, 2, 2, 1), "VALID")


def logits(params, x, arch, precision, dtype=jnp.float32):
    """x [B, H, W, C] -> [B, n_classes] float32. ``dtype`` is the type the
    operands of each convolution and product are rounded to."""
    pad = arch["padding"]
    h = _pool(jax.nn.relu(_conv(x, params["c1_w"], params["c1_b"], pad,
                                precision, dtype)))
    h = _pool(jax.nn.relu(_conv(h, params["c2_w"], params["c2_b"], pad,
                                precision, dtype)))
    h = h.reshape(h.shape[0], -1)
    h = jax.nn.relu(_dense(h, params["fc1_w"], precision, dtype)
                    + params["fc1_b"])
    return _dense(h, params["fc2_w"], precision, dtype) + params["fc2_b"]


def _dense(x, w, precision, dtype):
    return jnp.dot(x.astype(dtype), w.astype(dtype),
                   precision=precision).astype(jnp.float32)


def loss(params, x, y, arch, precision, dtype=jnp.float32):
    z = logits(params, x, arch, precision, dtype)
    nll = jax.nn.logsumexp(z, axis=-1) - jnp.take_along_axis(
        z, y[:, None], axis=-1)[:, 0]
    return jnp.mean(nll)


def layer_flops(arch: dict) -> list[tuple[str, int]]:
    """Multiply-add FLOPs (2 per MAC) of each conv and dense layer's
    forward pass for one sample."""
    hw, k, cin = arch["input_hw"], arch["kernel"], arch["in_channels"]
    out = []
    for i, cout in enumerate(arch["conv_channels"]):
        o = hw - k + 1 if arch["padding"] == "VALID" else hw
        out.append((f"c{i + 1}", 2 * o * o * k * k * cin * cout))
        hw, cin = o // 2, cout
    flat = hw * hw * cin
    out.append(("fc1", 2 * flat * arch["fc_hidden"]))
    out.append(("fc2", 2 * arch["fc_hidden"] * arch["n_classes"]))
    return out


def train_flops_per_sample(arch: dict) -> int:
    """Forward plus backward FLOPs one training sample requires: each
    layer's forward product, its weight gradient (the same count) and its
    input gradient (the same count), except the first layer, whose input
    needs no gradient. Elementwise work (bias, relu, pooling, softmax) is
    not counted."""
    fl = layer_flops(arch)
    return 3 * sum(f for _, f in fl) - fl[0][1]
