"""The fused bottleneck kernels' share of their roofline.

Over the traced window's top device ops named for a block's place, as the
program names them (``s<stage>b<block>[_ds].<n>``: stages from 1, blocks
from 0, ``_ds`` on a stage's first block, whose 1x1 projection runs in the
same kernel), the sum of each one's least time at the chip's peaks over
their device seconds.  A block's least time is the larger of its int8
operations for the window's completed images over the int8 peak, and its
least bytes over the HBM bandwidth: its u8 input and output per image, and
its s8 weights and s16 biases once per forward call.  The block shapes
come from the family reference's ``build_net``, not from the program; a
configuration without ``stage_blocks`` has none."""
import re

PLACE = re.compile(r"^s(\d+)b(\d+)(_ds)?(?:\.\d+)?$")


def _blocks(net, stage_blocks):
    """Place name -> (ops per image, bytes per image, bytes per call)."""
    out, i = {}, 0
    for stage, n in enumerate(stage_blocks):
        for j in range(n):
            b = net.blocks[i]
            convs = [b.conv0, b.conv1, b.conv2] + ([b.ds] if b.ds else [])
            name = f"s{stage + 1}b{j}" + ("_ds" if b.ds else "")
            out[name] = (
                2 * sum(c.k * c.k * c.cin * c.cout * c.hout * c.hout
                        for c in convs),
                b.conv0.hin ** 2 * b.conv0.cin
                + b.conv2.hout ** 2 * b.conv2.cout,
                sum(c.k * c.k * c.cin * c.cout + 2 * c.cout for c in convs))
            i += 1
    return out


def read(run):
    if run.trace is None or "stage_blocks" not in run.cell.config:
        return None
    cfg = run.cell.config
    work = _blocks(run.cell.family.reference.build_net(cfg),
                   cfg["stage_blocks"])
    w, peaks = run.window, run.peaks
    least = busy = 0.0
    for op, seconds in run.trace.top_ops:
        m = PLACE.match(op)
        name = m and f"s{m.group(1)}b{m.group(2)}{m.group(3) or ''}"
        if name not in work:
            continue
        ops, per_image, per_call = work[name]
        least += max(w.completed * ops / peaks["int8_ops_per_s"],
                     (w.completed * per_image + w.calls * per_call)
                     / peaks["hbm_bytes_per_s"])
        busy += seconds
    return 100.0 * least / busy if busy > 0 else None
