"""Operations and bytes one image needs, counted from the configuration's
shapes (the family's ``reference.build_net``), not from the program's
dataflow model.

``count(net)`` names no family.  It reads only this contract, which every
family's ``Net`` meets:

* ``net.convs()``: every conv of the network, each with ``k`` (square
  kernel side), ``cin``, ``cout``, ``hin`` (input side), ``stride`` and
  ``hout`` (output side);
* ``net.stem``: the first conv, whose ``hin`` and ``cin`` are the image's
  side and channels;
* ``net.fc_in`` and ``net.num_classes``: the fc head.

MACs: every conv (``k*k*cin*cout`` per output pixel, the 1x1 downsample
convs among them) and the fc (``fc_in*classes``).  Pooling layers count no
MACs.  One MAC is two operations.  Bytes, the least a forward must move:
the float32 image in, the float32 logits out, and once per forward call
every s8 weight, s16 conv bias and float32 fc bias.
"""
from __future__ import annotations

import dataclasses
import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


@dataclasses.dataclass(frozen=True)
class Work:
    macs_per_image: int
    in_bytes_per_image: int
    out_bytes_per_image: int
    weight_bytes: int            # read once per forward call

    @property
    def ops_per_image(self) -> int:
        return 2 * self.macs_per_image

    def bytes(self, images: int, calls: int) -> int:
        return images * (self.in_bytes_per_image + self.out_bytes_per_image) \
            + calls * self.weight_bytes


def count(net) -> Work:
    macs = 0
    weight_bytes = 0
    for c in net.convs():
        macs += c.k * c.k * c.cin * c.cout * c.hout * c.hout
        weight_bytes += c.k * c.k * c.cin * c.cout + 2 * c.cout   # s8 w, s16 b
    macs += net.fc_in * net.num_classes
    weight_bytes += net.fc_in * net.num_classes + 4 * net.num_classes
    side = net.stem.hin
    return Work(macs_per_image=macs,
                in_bytes_per_image=4 * side * side * net.stem.cin,
                out_bytes_per_image=4 * net.num_classes,
                weight_bytes=weight_bytes)


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``; an unknown kind
    is an error, never a default."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE}; known: {sorted(table)}")
    return table[device_kind]
