"""Host microseconds the batcher spends per submitted point over the
window: the dedup, lookup, fill and aggregate phases of its fused
dispatches (repro.obs histogram repro_batcher_phase_seconds; the eval
phase, the fresh points' evaluation, is left out) over the points
submitted (repro_batcher_points{kind=submitted}).  None where the program
lacks the phases."""

PHASES = ("dedup", "lookup", "fill", "aggregate")


def read(r):
    if "repro_batcher_phase_seconds" not in r.obs1:
        return None
    points = r.obs_delta("repro_batcher_points", "submitted")
    if points <= 0:
        return None
    host = sum(r.obs_delta("repro_batcher_phase_seconds", p, field="sum")
               for p in PHASES)
    return 1e6 * host / points
