"""scan_roofline.search: one f32 corpus pass per search at HBM bandwidth,
as a share of device busy time (layer: scoring kernel)."""

from perfbench.lib.layers import scan_roofline


def read(run):
    return scan_roofline(run, "search")
