"""Plain reference of the toy perceptron: numpy alone, nothing of any
program. ``y = tanh(x W1 + b1) W2 + b2``, mean squared error, one step of
gradient descent. ``precision`` "bf16" rounds every matrix product's
operands to bfloat16 (the nearest precision below the configuration's
float32), for the control."""

import numpy as np


def param_shapes(model: dict) -> dict:
    i, h, o = model["inputs"], model["hidden"], model["outputs"]
    return {"dense0/kernel": (i, h), "dense0/bias": (h,),
            "dense1/kernel": (h, o), "dense1/bias": (o,)}


def init(model: dict, seed: int) -> dict:
    rng = np.random.default_rng(seed)
    return {k: (rng.standard_normal(shape) / np.sqrt(shape[0])
                if k.endswith("kernel") else np.zeros(shape)
                ).astype(np.float32)
            for k, shape in param_shapes(model).items()}


def _operand(a, precision: str):
    if precision == "f32":
        return a
    bits = np.ascontiguousarray(a, np.float32).view(np.uint32)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & np.uint32(0xFFFF0000)
    return bits.view(np.float32)


def _dot(a, b, precision):
    return _operand(a, precision) @ _operand(b, precision)


def train_step(model: dict, lr: float, params: dict, x, y,
               precision: str = "f32"):
    """(params after the step, loss before it, the gradient)."""
    w1, b1 = params["dense0/kernel"], params["dense0/bias"]
    w2, b2 = params["dense1/kernel"], params["dense1/bias"]
    h = np.tanh(_dot(x, w1, precision) + b1)
    err = _dot(h, w2, precision) + b2 - y
    loss = float(np.mean(err ** 2))
    d_out = (2.0 / err.size) * err
    d_h = _dot(d_out, w2.T, precision) * (1.0 - h ** 2)
    grads = {"dense0/kernel": _dot(x.T, d_h, precision),
             "dense0/bias": d_h.sum(0),
             "dense1/kernel": _dot(h.T, d_out, precision),
             "dense1/bias": d_out.sum(0)}
    new = {k: (params[k] - lr * grads[k]).astype(np.float32) for k in params}
    return new, loss, grads
