"""Seconds JAX spent tracing, lowering and compiling (or loading from the
compile cache) inside the window, summed over threads: the repro.obs
histogram repro_jit_seconds over its three phases, each event counted
without the events nested in it.  None where the program lacks it."""

PHASES = ("trace", "lower", "compile")


def read(r):
    if "repro_jit_seconds" not in r.obs1:
        return None
    return sum(r.obs_delta("repro_jit_seconds", p, field="sum")
               for p in PHASES)
