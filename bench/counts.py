"""Operations and bytes the benchmark's work needs, counted from shapes.

These are the numerators of every utilization and roofline share the
benchmark reports. They count what the algorithm needs, whatever
implements it: a later change that does the same work with fewer bytes or
operations lowers the time, never the count.
"""
from __future__ import annotations

import json
import pathlib

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str, path=PEAKS) -> dict:
    """The published peaks of one chip of ``device_kind``. A device that
    is not in the table is an error: no share is computed against a
    guessed peak."""
    table = json.loads(pathlib.Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table)}")
    return table[device_kind]


def cnn_layers(image_shape, width: int, n_classes: int, hidden: int = 128):
    """(name, multiply-adds per sample) of the small CNN: two stride-2
    3x3 'SAME' convolutions (width, 2 * width channels), a dense layer of
    ``hidden`` units and the classifier."""
    h, w, c = image_shape
    f1, f2 = width, 2 * width
    h1, w1 = -(-h // 2), -(-w // 2)
    h2, w2 = -(-h1 // 2), -(-w1 // 2)
    return [("conv1", h1 * w1 * f1 * 9 * c),
            ("conv2", h2 * w2 * f2 * 9 * f1),
            ("dense", h2 * w2 * f2 * hidden),
            ("out", hidden * n_classes)]


def cnn_params(image_shape, width: int, n_classes: int,
               hidden: int = 128):
    """(weights and biases, tensors) of the small CNN."""
    h, w, c = image_shape
    f1, f2 = width, 2 * width
    flat = -(-(-(-h // 2)) // 2) * -(-(-(-w // 2)) // 2) * f2
    sizes = [9 * c * f1, f1, 9 * f1 * f2, f2, flat * hidden, hidden,
             hidden * n_classes, n_classes]
    return sum(sizes), len(sizes)


def cnn_forward_flops(image_shape, width: int, n_classes: int) -> int:
    """FLOPs of one sample's forward pass (2 per multiply-add; bias,
    activation and softmax work is left out)."""
    return sum(2 * m for _, m in cnn_layers(image_shape, width, n_classes))


def cnn_train_flops(image_shape, width: int, n_classes: int) -> int:
    """FLOPs of one sample's forward and backward pass: the forward pass,
    the weight gradients (as much again) and the activation gradients of
    every layer but the first, whose input is data."""
    layers = cnn_layers(image_shape, width, n_classes)
    fwd = sum(2 * m for _, m in layers)
    return 2 * fwd + fwd - 2 * layers[0][1]


def quant_agg_need(rows: int, params: int, leaves: int, bits: int):
    """(operations, bytes) the server's quantized mean of ``rows`` models
    needs: each weight of each row read at ``bits`` bits, one f32 scale
    per row and tensor, the f32 mean written once, one multiply-add per
    weight and row."""
    nbytes = rows * params * bits / 8 + rows * leaves * 4 + params * 4
    return 2 * rows * params, nbytes


def least_time_s(flops: float, nbytes: float, peak: dict) -> float:
    """The least time a chip with ``peak`` could take: the larger of the
    operations over the bf16 peak and the bytes over HBM bandwidth."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])


def mamba2_train_flops_per_token(d_model: int, n_layers: int, vocab: int,
                                 d_state: int, head_dim: int, expand: int,
                                 n_groups: int, conv_width: int,
                                 chunk: int) -> int:
    """Training FLOPs per token of a Mamba-2 language model with tied
    embeddings: 6 per weight of every matmul (in/out projections, the
    output head) and of the depthwise convolution, plus 3 times the
    chunked SSD's own forward operations (arXiv:2405.21060 section 6):
    per chunk of length Q, C B^T (Q x Q x N per group), its product with
    X (Q x Q x P per head), the chunk states B^T X and their read-out
    C h (N x P per head each)."""
    d_in = expand * d_model
    heads = d_in // head_dim
    gn = n_groups * d_state
    proj = d_model * (2 * d_in + 2 * gn + heads) + d_in * d_model
    conv = (d_in + 2 * gn) * conv_width
    ssd = (2 * chunk * d_state * n_groups + 2 * chunk * head_dim * heads
           + 2 * 2 * d_state * head_dim * heads)
    per_layer = 6 * (proj + conv) + 3 * ssd
    return n_layers * per_layer + 6 * vocab * d_model
