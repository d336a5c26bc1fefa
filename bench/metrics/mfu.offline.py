"""The whole step's share of the chips' int8 peak: images completed in the
window times the int8 operations one image needs (``work.py``), over the
window's seconds times the peak of every chip the cell drives."""


def read(run):
    ops = run.window.completed * run.work.ops_per_image
    peak = run.peaks["int8_ops_per_s"] * run.n_devices
    return 100.0 * ops / (run.window.seconds * peak)
