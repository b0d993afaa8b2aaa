"""``service_closed`` with ``fanout`` searches: one request runs
``n_shards`` seed-parallel REINFORCE searches, one per chip.

``fanout`` streams each shard's progress as its own ``Trial`` tagged with
``Trial.shard``, and a Trial's ``step`` counts that shard's evaluations
alone.  So a search's evaluations so far are the sum over shards of each
shard's latest ``step``: that sum is what the window's progress marks
record.

Besides ``best_gap`` on each answer's best design, every shard's training
is checked (``rl_value_gap``): shard ``s`` of a request with seed ``n`` is
a REINFORCE search from seed ``n + s``, so its first epochs' episode
values must match :mod:`chipbench.rl_reference`'s replay from that seed.
``fanout`` returns only the merged answer, so the driver keeps each
shard's epoch history as the device backend's engine hands it to
``fanout`` (``dist_search._DEVICE_ENGINES``), keyed by the request's seed.
"""
from __future__ import annotations

from chipbench.drivers import Outcome
from chipbench.drivers.service_closed import ServiceClosed


class FanoutClosed(ServiceClosed):

    def __init__(self, ctx):
        super().__init__(ctx)
        self.shard_histories = {}     # request seed -> [history per shard]
        self._engines = None

    def setup(self):
        from repro.distributed import dist_search
        engines = dist_search._DEVICE_ENGINES
        inner = engines["reinforce"]

        def recording(subs):
            shards = inner(subs)
            self.shard_histories[subs[0].seed] = [
                o.extras["history"] for o in shards]
            return shards

        self._engines = (engines, inner)
        engines["reinforce"] = recording
        super().setup()

    def close(self):
        super().close()
        if self._engines is not None:
            engines, inner = self._engines
            engines["reinforce"] = inner
            self._engines = None

    def request(self, spec, on_progress=None):
        if on_progress is not None:
            per_shard, report = {}, on_progress

            def on_progress(tr):
                per_shard[tr.shard] = tr.step
                report(tr._replace(step=sum(per_shard.values())))

        return super().request(spec, on_progress)

    def rl_value_gap(self, control: bool) -> float:
        """The widest gap over every shard of every answer; an answer
        whose shards left no history reads as an infinite gap."""
        shards = []
        for o in self.outcomes:
            hists = self.shard_histories.get(o.spec["seed"])
            if not hists:
                return float("inf")
            shards += [Outcome(dict(o.spec, seed=o.spec["seed"] + s),
                               o.best_value, o.pe, o.kt, o.df, h)
                       for s, h in enumerate(hists)]
        answers, self.outcomes = self.outcomes, shards
        try:
            return super().rl_value_gap(control)
        finally:
            self.outcomes = answers


DRIVER = FanoutClosed
