"""The population engines' own host milliseconds per generation over the
window: the repro.obs histograms repro_search_step_seconds less
repro_search_eval_wait_seconds (the wait on the batcher), summed over
engines, over the generations.  That is decoding the population,
dispatching evolve and fetching the next population.  None where the
program lacks them."""

STEP = "repro_search_step_seconds"
WAIT = "repro_search_eval_wait_seconds"


def read(r):
    if STEP not in r.obs1 or WAIT not in r.obs1:
        return None
    engines = r.obs1[STEP].get("values", {})
    steps = sum(r.obs_delta(STEP, e, field="count") for e in engines)
    if steps <= 0:
        return None
    own = sum(r.obs_delta(STEP, e, field="sum")
              - r.obs_delta(WAIT, e, field="sum") for e in engines)
    return 1e3 * own / steps
