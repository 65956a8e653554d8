"""Operations and bytes a step needs, computed from shapes.

``forward_flops`` counts the multiply-adds of a configuration's convs and
dense layers, two operations each, for one sample: the work a forward pass
requires. A SAME conv's taps that fall on its zero padding are not
counted (they add nothing), and neither are bias adds, ReLUs and pools. A
training step is three forward passes' worth (the forward and the two
products of the backward pass).
"""
from __future__ import annotations


def _taps(n: int, k: int) -> int:
    """Kernel taps inside an n-long input, summed over a stride-1 SAME
    conv's n outputs."""
    pad = (k - 1) // 2
    return sum(min(o - pad + k, n) - max(o - pad, 0) for o in range(n))


def forward_flops(config: dict) -> float:
    H, W, C = config["input_hw"]
    k = int(config["cnn_kernel"])
    flops = 0
    cin = C
    for ch in config["cnn_channels"]:
        flops += 2 * _taps(H, k) * _taps(W, k) * cin * ch
        cin = ch
        H, W = H // 2, W // 2                      # 2x2 max-pool
    dims = [H * W * cin] + list(config["mlp_hidden"]) + [config["num_classes"]]
    flops += sum(2 * a * b for a, b in zip(dims[:-1], dims[1:]))
    return float(flops)


def train_flops(config: dict, samples: float) -> float:
    """Operations of SGD over ``samples`` real samples."""
    return 3.0 * forward_flops(config) * samples


def sens_sketch_bytes(d: int, rows: int) -> float:
    """``sens_sketch``: per sketched model reads theta, g and the Fisher
    diagonal, three f32 vectors of the model's size."""
    return float(rows) * 3.0 * d * 4


def params(config: dict) -> int:
    H, W, C = config["input_hw"]
    k = int(config["cnn_kernel"])
    n = 0
    cin = C
    for ch in config["cnn_channels"]:
        n += k * k * cin * ch + ch
        cin = ch
        H, W = H // 2, W // 2
    dims = [H * W * cin] + list(config["mlp_hidden"]) + [config["num_classes"]]
    n += sum(a * b + b for a, b in zip(dims[:-1], dims[1:]))
    return int(n)


def check(config: dict) -> None:
    """The configuration's shapes give the parameter count it states."""
    if params(config) != int(config["params"]):
        raise ValueError(f"{config['name']}: shapes give {params(config)} "
                         f"parameters, the file states {config['params']}")
