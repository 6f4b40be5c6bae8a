"""Plain reference forward of the Nature-CNN policy, and the comparison.

Straightforward `jax.numpy`, float32 throughout, matrix precision
"highest", no flax, no bf16: Mnih et al. 2015's trunk (three VALID
convolutions with ReLU, a 512-unit dense layer with ReLU) and two linear
heads on the hidden vector, as `ray_tpu/models/networks.py` VisionNetwork
describes itself. Departure from the paper: observations are scaled by
1/255 inside the network, as the system does.

Tolerance. The system computes the trunk in bfloat16 (8 bits of mantissa,
~0.4 % per rounding) by design and the heads in float32, so it cannot agree
with this reference to float32 accuracy. Both outputs are compared as the
largest absolute difference over the largest absolute reference value, per
output. On the v5e the four bf16 layers came to 0.34-0.83 % of the
output's scale over the runs of PR 24 (0.08-0.25 % on the CPU);
`TOLERANCE` is 2.5 %, three times the largest error seen. A dropped or
re-ordered layer, a missing ReLU or a wrong scaling moves the outputs by
tens of per cent of their scale; an int8 or fp8 trunk rounds at 3-6 % a
layer and fails as well. A float32 trunk passes: it is closer, not wrong.
"""

import jax
import jax.numpy as jnp
import numpy as np

TOLERANCE = 0.025


def _conv(x, kernel, bias, stride):
    y = jax.lax.conv_general_dilated(
        x, kernel, window_strides=(stride, stride), padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + bias


def forward(params: dict, obs, strides) -> tuple:
    """(logits [B, A], value [B]) for uint8 obs [B, H, W, C].

    `params` maps layer name -> {"kernel", "bias"} for conv_0.., fc,
    logits, value (the system's own parameter tree, cast to float32)."""
    p = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(obs, jnp.float32) / 255.0
        for i, stride in enumerate(strides):
            layer = p[f"conv_{i}"]
            x = jax.nn.relu(_conv(x, layer["kernel"], layer["bias"], stride))
        x = x.reshape(x.shape[0], -1)
        h = jax.nn.relu(x @ p["fc"]["kernel"] + p["fc"]["bias"])
        logits = h @ p["logits"]["kernel"] + p["logits"]["bias"]
        value = (h @ p["value"]["kernel"] + p["value"]["bias"])[:, 0]
    return logits, value


def relative_error(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return float("inf")
    scale = float(np.max(np.abs(want)))
    return float(np.max(np.abs(got - want)) / max(scale, 1e-12))


def compare(system_out, reference_out) -> dict:
    """Per-output relative error and the verdict."""
    errs = {name: relative_error(g, w) for name, g, w in zip(
        ("logits", "value"), system_out, reference_out)}
    return {"errors": errs, "tolerance": TOLERANCE,
            "ok": all(e <= TOLERANCE for e in errs.values())}
