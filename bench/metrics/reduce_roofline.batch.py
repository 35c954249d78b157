"""Reduce bytes (read the received copies, write the result rows) over HBM
bandwidth, as a share of the reduce's device time summed over the chips."""
from bench import roofline


def read(run):
    t = run.trace
    if t is None:
        return None
    total = sum(roofline.reduce_bytes(run.widths[s.spec.tenant], sum(s.recv),
                                      s.out_rows, run.out_width[s.spec.tenant])
                for s in run.completed())
    return roofline.roofline_pct(
        total, t.phase_s(("join_probe", "expand_rows")) * run.chips,
        run.peaks["hbm_bytes_per_s"])
