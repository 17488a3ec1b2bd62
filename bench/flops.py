"""Analytic operation counts of the segmentation student.

`forward_flops` counts the multiply-adds (2 operations each) of every
convolution and of the bilinear upsampling of one image's forward pass,
from the configuration's shapes alone. Training counts three forward
passes per image (forward plus a backward pass of twice its cost); work
recomputed by the program does not count.
"""
from __future__ import annotations


def _ch(student: dict, c: int) -> int:
    return max(8, int(round(c * student["width"])))


def _out(n: int, stride: int) -> int:
    return -(-n // stride)


def forward_flops(student: dict, height: int, width: int) -> int:
    """Operations of one image's forward pass at ``height`` x ``width``."""
    total = 0

    def conv(h, w, k, cin_per_group, cout):
        return 2 * h * w * k * k * cin_per_group * cout

    h, w = _out(height, 2), _out(width, 2)
    c = _ch(student, student["stem"])
    total += conv(h, w, 3, student["in_channels"], c)
    for exp, out, stride in student["blocks"]:
        hidden, c_out = c * exp, _ch(student, out)
        total += conv(h, w, 1, c, hidden)
        h, w = _out(h, stride), _out(w, stride)
        total += conv(h, w, 3, 1, hidden)
        total += conv(h, w, 1, hidden, c_out)
        c = c_out
    head, n_cls = _ch(student, student["head"]), student["n_classes"]
    total += conv(h, w, 1, c, head)  # local 1x1
    total += conv(1, 1, 1, c, head)  # global-context 1x1
    total += conv(h, w, 1, head, n_cls)
    # bilinear upsampling as two contractions: rows, then columns
    total += 2 * height * h * w * n_cls + 2 * height * width * w * n_cls
    return total


def train_flops_per_image(student: dict, height: int, width: int) -> int:
    return 3 * forward_flops(student, height, width)
