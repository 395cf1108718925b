"""Share of the roofline that the window's convolutions reached: the least
time the chip could take for them (``lib/flops.step_floor_seconds``: per
convolution and pass, the larger of operations over the peak rate and bytes
over the peak bandwidth) over the time the trace shows in the operations
that hold a convolution. Those are: an operation whose name or opcode says
"conv" (XLA's ``convolution``, fusions XLA names after one, the program's
Pallas kernels ``conv3x3_*``), and an output fusion with an operand or a
result of the shape of one of the configuration's kernels -- XLA's TPU
backend gives most convolution fusions a bare ``fusion.N``. What XLA fused
into them (norm, ReLU, casts, the Adam update of a kernel's gradient)
counts as their time; layout copies and pads around them do not. Both
sides cover the train steps (three passes) and the validation batches
(forward only)."""

import re

from perfbench.lib import flops

_CONV = re.compile(r"conv(?!ert)")      # "convolution", "conv3x3", not "convert"


def kernel_shapes(model: dict, size: int) -> set:
    """The dimensions of every convolution kernel, as a sorted tuple: the
    gradient passes see them in other orders."""
    out = set()
    for ly in flops.conv_layers(model, size):
        k = 2 if ly["name"].endswith("tconv") else int(ly["taps"] ** 0.5)
        out.add(tuple(sorted((k, k, ly["cin"], ly["cout"]))))
    return out


def holds_conv(name: str, kernels: set) -> bool:
    words = name.split(" ")
    if any(_CONV.search(w.lower()) for w in words[:2]):
        return True
    if "kOutput" not in words:
        return False
    for shape in words[words.index("kOutput") + 1:]:
        if tuple(sorted(int(d) for d in shape.split("x"))) in kernels:
            return True
    return False


def conv_seconds(op_seconds: dict, kernels: set) -> float:
    return sum(s for name, s in op_seconds.items()
               if holds_conv(name, kernels))


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    steps = ctx.counters.get("optimizer_steps")
    model = ctx.cell.config["model"]
    size = ctx.cell.config["train"]["img_size"]
    measured = conv_seconds(ctx.trace.op_seconds, kernel_shapes(model, size))
    if measured <= 0 or not steps:
        return None
    batch = ctx.counters["batch"]
    floor = steps * flops.step_floor_seconds(model, size, batch, ctx.peaks)
    floor += ctx.counters.get("eval_batches", 0) * flops.step_floor_seconds(
        model, size, batch, ctx.peaks, train=False)
    return 100.0 * floor / measured
