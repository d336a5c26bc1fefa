"""The forward's least time at the chip's peaks over the device's busy time
in the trace.  The least time is the larger of the window's int8
operations over the int8 peak and its least bytes (images in, logits out,
the weights once per forward call) over the HBM bandwidth."""
import sys


def read(run):
    if run.trace is None or run.trace.busy_s <= 0:
        return None
    w = run.window
    ops_s = w.completed * run.work.ops_per_image / run.peaks["int8_ops_per_s"]
    bytes_s = run.work.bytes(w.completed, w.calls) / \
        run.peaks["hbm_bytes_per_s"]
    print(f"[bench] step_roofline: least time {max(ops_s, bytes_s):.6f} s, "
          f"bound by {'int8 ops' if ops_s >= bytes_s else 'HBM bytes'} "
          f"(ops {ops_s:.6f} s, bytes {bytes_s:.6f} s), device busy "
          f"{run.trace.busy_s:.6f} s", file=sys.stderr)
    return 100.0 * max(ops_s, bytes_s) / (run.trace.busy_s * run.n_devices)
