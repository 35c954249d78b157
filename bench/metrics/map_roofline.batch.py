"""Map bytes (read the relations, write the routed copies) over HBM
bandwidth, as a share of the map's device time summed over the chips."""
from bench import roofline


def read(run):
    t = run.trace
    if t is None:
        return None
    total = sum(roofline.map_bytes(s.rows_in, run.widths[s.spec.tenant],
                                   sum(s.recv)) for s in run.completed())
    return roofline.roofline_pct(total, t.phase_s(("scatter_pack",))
                                 * run.chips, run.peaks["hbm_bytes_per_s"])
