"""Share of the fresh points the batcher handed to the cost evaluator over
the window whose layer type is BMM or EXPERTS, in percent: the repro.obs
counter repro_batcher_fresh_points{ltype}.  None where the program lacks
the counter or evaluated no fresh point."""

NAME = "repro_batcher_fresh_points"
INSTANCE_TYPES = ("bmm", "experts")


def read(r):
    if NAME not in r.obs1:
        return None
    total = sum(r.obs_delta(NAME, t) for t in r.obs1[NAME].get("values", {}))
    if total <= 0:
        return None
    inst = sum(r.obs_delta(NAME, t) for t in INSTANCE_TYPES)
    return 100.0 * inst / total
