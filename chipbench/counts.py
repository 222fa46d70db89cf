"""Operation and byte counts, from shapes alone.

Nothing here looks at a compiled program: the counts are what the
algorithm needs, so they stay put when the program changes.

* Model FLOPs count the multiply-adds of convolutions and matrix
  products (2 FLOPs each). Normalisation, activations and pooling are
  left out, and so is any recomputation.
* Training a sample costs its forward three times over (forward,
  gradient of the activations, gradient of the weights).
* `topk_wire_*` count the top-k wire kernel's least work: the logits
  are read once, and ``2k + 1`` values are written per row (k values,
  k indices, one logsumexp). The count does not depend on what
  implements top-k.
"""
from __future__ import annotations

from typing import Sequence


def _same_out(size: int, stride: int) -> int:
    return -(-size // stride)  # "SAME" padding: ceil(size / stride)


def resnet_forward_macs(image_size: int, stage_sizes: Sequence[int],
                        width: int, num_classes: int, num_aux_heads: int,
                        stem_kernel: int = 3, stem_stride: int = 2,
                        in_channels: int = 3) -> int:
    """Multiply-adds of one image's forward through a basic-block ResNet:
    the stem convolution, a 3x3/2 max-pool when the stem strides, two 3x3
    convolutions per block, a 1x1 projection where the width changes, and
    the main and aux heads over the pooled embedding."""
    hw = _same_out(image_size, stem_stride)
    macs = hw * hw * stem_kernel * stem_kernel * in_channels * width
    if stem_stride == 2:
        hw = _same_out(hw, 2)
    cin = width
    for si, n_blocks in enumerate(stage_sizes):
        cout = width * 2 ** si
        for bi in range(n_blocks):
            stride = 2 if (si > 0 and bi == 0) else 1
            out = _same_out(hw, stride)
            macs += out * out * 9 * cin * cout  # conv1
            macs += out * out * 9 * cout * cout  # conv2
            if cin != cout:
                macs += out * out * cin * cout  # 1x1 projection
            hw, cin = out, cout
    macs += cin * num_classes * (1 + num_aux_heads)
    return macs


def mhd_fleet_step_flops(forward_flops_per_sample: float, clients: int,
                         private_batch: int, public_batch: int,
                         publish_window: int, publish_every: int,
                         distill_share: float = 1.0) -> float:
    """Model FLOPs of one fleet step: a client step that distills trains
    on the private and the public batch, one that falls back to a
    supervised step (``1 - distill_share`` of them) on the private batch
    alone; every client publishes ``publish_window`` forwards of a public
    batch every ``publish_every`` steps (amortised per step)."""
    train = 3 * forward_flops_per_sample * (
        private_batch + distill_share * public_batch)
    publish = forward_flops_per_sample * public_batch * publish_window \
        / publish_every
    return clients * (train + publish)


def topk_wire_bytes(rows: int, vocab: int, k: int,
                    in_bytes: int = 4) -> int:
    """Least HBM traffic of one top-k wire call over ``rows`` x ``vocab``
    logits: read once, ``2k + 1`` four-byte values written per row."""
    return rows * vocab * in_bytes + rows * (2 * k + 1) * 4


def topk_wire_ops(rows: int, vocab: int) -> int:
    """Least operations: a logsumexp (max, subtract, exp, add) and one
    selection pass over every logit."""
    return rows * vocab * 5
