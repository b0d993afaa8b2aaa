"""Share of its roofline that the evaluation of fresh cost-model points
reaches.  Work: the fresh points the batcher evaluated in the traced window
(from the benchmark's annotation around each call), each reading 8 layer
fields and 3 inputs and writing 4 outputs in float32.  Time: the device's
busy time inside those annotations, whatever programs ran there.  Bytes
bound it: least time = bytes / HBM bandwidth."""
from chipbench import peaks, work

PREFIX = "chipbench.eval_points"


def read(r):
    points = sum(int(n.split(":")[1]) for _, _, n in
                 r.trace.annotations(PREFIX))
    t = r.trace.busy_under(PREFIX)
    if points == 0 or t <= 0:
        return None
    bw = peaks.peaks(r.device_kind)["hbm_bytes_per_s"]
    return 100.0 * work.cost_eval_bytes(points) / bw / t
