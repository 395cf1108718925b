"""Operations and bytes of a configuration's convolutions, from its shapes.

Counted per convolution and per pass (forward; gradient of the input;
gradient of the kernel): 2 * output pixels * taps * cin * cout operations,
and one read of each operand plus one write of the result in the compute
type's width. The first convolution's input gradient is not needed (its
input is data) and is not counted. Bilinear up-sampling, pooling, norms and
the optimiser are not convolutions and are not counted here.
"""

from __future__ import annotations


def conv_layers(model: dict, img: int) -> list[dict]:
    """Every convolution of the U-Net that ``model`` describes, with its
    output size ``hw`` (a side), input side ``hw_in``, taps per output
    pixel, and channel counts."""
    f, bil = model["base_features"], model["bilinear"]
    factor = 2 if bil else 1
    enc = [f, 2 * f, 4 * f, 8 * f, 16 * f // factor]
    layers = []

    def double(name, hw, cin, mid, cout):
        layers.append(dict(name=f"{name}.conv0", hw=hw, hw_in=hw, taps=9,
                           cin=cin, cout=mid))
        layers.append(dict(name=f"{name}.conv1", hw=hw, hw_in=hw, taps=9,
                           cin=mid, cout=cout))

    double("enc0", img, model["in_channels"], f, f)
    for i in range(4):
        double(f"enc{i + 1}", img >> (i + 1), enc[i], enc[i + 1], enc[i + 1])
    x_ch = enc[4]
    for i, out in enumerate([8 * f // factor, 4 * f // factor,
                             2 * f // factor, f]):
        hw, skip = img >> (3 - i), enc[3 - i]
        if bil:
            double(f"dec{i}", hw, x_ch + skip, (x_ch + skip) // 2, out)
        else:
            layers.append(dict(name=f"dec{i}.tconv", hw=hw, hw_in=hw // 2,
                               taps=1, cin=x_ch, cout=x_ch // 2))
            double(f"dec{i}", hw, x_ch // 2 + skip, out, out)
        x_ch = out
    layers.append(dict(name="head", hw=img, hw_in=img, taps=1, cin=f,
                       cout=model["num_classes"]))
    layers[0]["first"] = True
    return layers


def pass_flops(layer: dict, batch: int) -> float:
    return 2.0 * batch * layer["hw"] ** 2 * layer["taps"] * layer["cin"] \
        * layer["cout"]


def pass_bytes(layer: dict, batch: int, width: int = 2) -> float:
    acts = batch * (layer["hw_in"] ** 2 * layer["cin"]
                    + layer["hw"] ** 2 * layer["cout"])
    taps = 4 if layer["name"].endswith("tconv") else layer["taps"]
    return float(width * (acts + taps * layer["cin"] * layer["cout"]))


def passes(layer: dict, train: bool) -> int:
    if not train:
        return 1
    return 2 if layer.get("first") else 3


def forward_flops(model: dict, img: int, batch: int = 1) -> float:
    return sum(pass_flops(ly, batch) for ly in conv_layers(model, img))


def step_flops(model: dict, img: int, batch: int, train: bool = True) -> float:
    return sum(passes(ly, train) * pass_flops(ly, batch)
               for ly in conv_layers(model, img))


def step_floor_seconds(model: dict, img: int, batch: int, peaks: dict,
                       train: bool = True) -> float:
    """The least time the chip could take for one batch's convolutions:
    per convolution and pass, the larger of operations over the peak rate
    and bytes over the peak bandwidth, summed (they run one after another)."""
    return sum(
        passes(ly, train) * max(pass_flops(ly, batch) / peaks["flops_per_s"],
                                pass_bytes(ly, batch) / peaks["bytes_per_s"])
        for ly in conv_layers(model, img))
