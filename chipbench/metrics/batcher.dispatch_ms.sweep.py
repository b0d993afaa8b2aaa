"""Mean host wall time of one fused batcher dispatch over the window, in
ms: the repro.obs histogram repro_batcher_dispatch_seconds."""


def read(r):
    n = r.obs_delta("repro_batcher_dispatch_seconds", field="count")
    if n <= 0:
        return None
    return 1e3 * r.obs_delta("repro_batcher_dispatch_seconds",
                             field="sum") / n
