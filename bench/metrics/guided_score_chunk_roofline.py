"""Share of the HBM roofline the fused chunk kernels reach on the long
route: the least time the useful bytes need at the chip's peak
bandwidth, over the device time of the kernels in the trace.

Useful bytes are the postings the long route scored (each request's
``postings_touched``) times the configuration's bytes per posting, plus
the per-(term, tile) metadata of the tiles it visited (``tiles_visited``
x live terms x bytes per run), over the requests the window answered
(the trace covers the whole window and its drain). Padding slots, the
decode form and the tiling earn nothing, so the same work counts the
same whatever implements it. The
kernels have no stable name of their own yet: the trace names their
custom calls after the jitted functions that launch them
(``..._guided_score_chunk__.N``)."""
from lsrbench import xtrace

KERNELS = ("guided_score_chunk",)


def read(run):
    if run["events"] is None:
        return None
    seconds = xtrace.kernel_s(run["events"], KERNELS)
    roof = run["config"]["roofline"]
    useful = sum(r["stats"]["postings_touched"] * roof["bytes_per_posting"]
                 + r["stats"]["tiles_visited"] * r["live_terms"]
                 * roof["bytes_per_run"]
                 for r in run["records"]
                 if r is not None and r["route"] == "long")
    if seconds <= 0 or useful <= 0:
        return None
    return 100.0 * useful / run["peaks"]["hbm_bytes_per_s"] / seconds
