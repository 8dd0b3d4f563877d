"""Operation and byte counts from shapes: the yardstick behind ``mfu`` and
the kernels' roofline shares.

An operation is a multiply or an add (a multiply-add counts two). The counts
are what the algorithm needs, not what a program happens to compute: no
padding, no recomputation.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, List, Tuple

VGG11_PLAN = [64, "M", 128, "M", 256, 256, "M", 512, 512, "M", 512, 512, "M"]
PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> Dict[str, float]:
    """Peak FLOP/s and HBM bytes/s of one chip; a kind that is not in the
    table is an error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add it to "
                       f"{PEAKS_FILE.name} with its source")
    return table[device_kind]


def vgg11_layers(width_mult: float = 1.0, classes: int = 10,
                 image: int = 32) -> List[Tuple[str, dict]]:
    """VGG-11's weighted layers in order: ("conv", {ci, co, hw}) with 3x3
    'SAME' kernels at stride 1, then ("fc", {si, so})."""
    out, ci, hw = [], 3, image
    for item in VGG11_PLAN:
        if item == "M":
            hw //= 2
            continue
        co = max(1, int(item * width_mult))
        out.append(("conv", {"ci": ci, "co": co, "hw": hw}))
        ci = co
    fc1 = max(16, int(4096 * width_mult))
    for si, so in [(ci * hw * hw, fc1), (fc1, fc1), (fc1, classes)]:
        out.append(("fc", {"si": si, "so": so}))
    return out


def _layer_macs(kind: str, d: dict) -> int:
    if kind == "conv":
        return 9 * d["ci"] * d["co"] * d["hw"] * d["hw"]
    return d["si"] * d["so"]


def vgg11_forward_flops(width_mult: float = 1.0, classes: int = 10) -> int:
    """Forward operations per sample: 2 x multiply-adds of the convolutions
    and fc layers (bias, relu and pooling are left out)."""
    return sum(2 * _layer_macs(k, d)
               for k, d in vgg11_layers(width_mult, classes))


def vgg11_train_flops(width_mult: float = 1.0, classes: int = 10) -> int:
    """Forward + backward operations per sample: each layer's forward, its
    weight gradient, and its input gradient, except the first layer's,
    which nothing needs."""
    layers = vgg11_layers(width_mult, classes)
    total = 0
    for i, (k, d) in enumerate(layers):
        f = 2 * _layer_macs(k, d)
        total += f + f + (f if i > 0 else 0)
    return total


def gemm(m: int, k: int, n: int, itemsize: int = 4) -> Tuple[int, int]:
    """(operations, bytes) of one (m, k) x (k, n) product: every operand
    read once and the result written once."""
    return 2 * m * k * n, itemsize * (m * k + k * n + m * n)


def fc_kernel_calls(rows: int, width_mult: float = 1.0, classes: int = 10,
                    itemsize: int = 4, copies: int = 1
                    ) -> List[Tuple[str, int, int]]:
    """The fc layers' three training GEMMs on a batch of ``rows``:
    forward ``x @ w`` (plus the bias row), input gradient ``dz @ w^T`` and
    weight gradient ``x^T @ dz`` (plus the bias gradient's column sums).
    ``copies`` independent problems of that size run in one call (one per
    device slot, each with its own weights). Returns (name, operations,
    bytes) per call."""
    out = []
    fcs = [d for k, d in vgg11_layers(width_mult, classes) if k == "fc"]
    for i, d in enumerate(fcs):
        si, so = d["si"], d["so"]
        f_ops, f_bytes = gemm(rows, si, so, itemsize)
        out.append((f"fc{i}.fwd", f_ops + rows * so,
                    f_bytes + itemsize * so))
        dx_ops, dx_bytes = gemm(rows, so, si, itemsize)
        out.append((f"fc{i}.dx", dx_ops, dx_bytes))
        dw_ops, dw_bytes = gemm(si, rows, so, itemsize)
        out.append((f"fc{i}.dw", dw_ops + rows * so,
                    dw_bytes + itemsize * so))
    return [(n, copies * o, copies * b) for n, o, b in out]


def least_time(ops: float, nbytes: float, peak: Dict[str, float]
               ) -> Tuple[float, str]:
    """The roofline: the least time the chip could take, and which bound
    sets it ("compute" or "memory")."""
    t_c = ops / peak["flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
